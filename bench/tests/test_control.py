"""The control: the reference put in the program's place in bfloat16, the
precision below the configurations' float32, must come out not correct.
On the chip it is read at each cell's own size by ``bench/tools/readings.py``;
here at a size a test run holds."""
import jax.numpy as jnp
import pytest

from bench.harness import check, reference
from bench.tests import tiny

SEED = 2**33 + 17


@pytest.mark.parametrize("name", ["yi6b.train", "mamba2.train"])
def test_bf16_control_is_not_correct(name):
    c = tiny.cell(name)
    ref = reference.run(c.model, c.config, c.traffic, SEED)
    again = reference.run(c.model, c.config, c.traffic, SEED)
    ok, _ = check.verdict(check.numbers(again, ref), c.limits)
    assert ok, "the reference must agree with itself"
    ctl = reference.run(c.model, c.config, c.traffic, SEED,
                        dtype=jnp.bfloat16, precision=None)
    ok, checks = check.verdict(check.numbers(ctl, ref), c.limits)
    assert not ok, checks
