"""Seconds the program spent compiling its train step in the run: the sum
of its ``trainer.compile`` spans, at the build and at any rescale."""
from bench.metrics import _spans


def read(rec):
    return _spans.total("trainer.compile")
