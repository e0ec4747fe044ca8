"""The window's share of the held chips' bf16 peak: the benchmark's FLOP
count per step (forward and backward, no recompute) times the steps, over
the chip-seconds held times the peak."""


def read(rec):
    if not rec.step_s or rec.peak is None:
        return None
    chip_s = sum((b - a) * w for a, b, w in rec.held)
    flops = rec.flops_per_step * len(rec.step_s)
    return 100.0 * flops / (chip_s * rec.peak["bf16_flops_per_s"])
