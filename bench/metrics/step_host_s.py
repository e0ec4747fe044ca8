"""Host work before the device can start a step: the median over the
window's steps of ``trainer.batch`` + ``trainer.put`` + ``trainer.dispatch``
(the program's own spans)."""
from bench.metrics import _spans


def read(rec):
    return _spans.median_per_step(
        rec, ("trainer.batch", "trainer.put", "trainer.dispatch"))
