"""Process start to the first timed step: imports, trainer build and
compile, weights, the checked and warm-up steps."""


def read(rec):
    return rec.setup_s
