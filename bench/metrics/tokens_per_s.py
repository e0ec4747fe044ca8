"""Tokens trained in the window over the window's seconds, every stall in."""


def read(rec):
    return len(rec.step_s) * rec.tokens_per_step / rec.window_s
