"""Reading a step's metrics back once they are ready: the median over the
window's steps of ``trainer.readback`` (the program's own span)."""
from bench.metrics import _spans


def read(rec):
    return _spans.median_per_step(rec, ("trainer.readback",))
