"""Seconds the trainer spent making its own weights and AdamW state, which
the benchmark then replaces: the sum of its ``trainer.init`` spans."""
from bench.metrics import _spans


def read(rec):
    return _spans.total("trainer.init")
