"""The breakdown tool (``bench/tools/breakdown.py``): self times of nested
ops, scopes read from HLO text, and a report on a trace made on the CPU."""

import jax
import pytest

from bench.harness import driver
from bench.harness import trace as T
from bench.tests import tiny
from bench.tools import breakdown as B

HLO = """\
ENTRY %main.1 (x.1: f32[8,16]) -> f32[8,16] {
  %dot_general.8 = f32[8,16]{1,0} dot(%x.1, %w.1), metadata={op_name="jit(f)/jvp(mixer)/dot_general" source_file="m.py"}
  ROOT %sub.3 = f32[16,16]{1,0} subtract(%p, %m), metadata={op_name="jit(f)/optimizer/sub"}
  %fusion.4 = f32[4,16]{1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(f)/transpose(jvp(head))/while/body/closed_call/checkpoint/norm/mul"}
  %while.11 = (s32[], f32[]) while(%t), body=%body, metadata={op_name="jit(f)/jvp(head)/while"}
  %copy.2 = f32[8,16]{1,0} copy(%x.1), metadata={op_name="jit(f)/while"}
  %param.1 = f32[8,16]{1,0} parameter(0)
}
"""


def test_scopes_of_takes_the_innermost_scope():
    assert B.scopes_of(HLO) == {"dot_general.8": "mixer", "sub.3": "optimizer",
                                "fusion.4": "norm", "while.11": "head"}


def test_self_times_subtract_nested_ops():
    ops = [(0.0, 10.0, "while.1"), (1.0, 3.0, "a"), (4.0, 9.0, "while.2"),
           (5.0, 6.0, "b"), (7.0, 8.0, "c"), (12.0, 13.0, "d")]
    own = dict(B.self_times(ops, 0.0, 20.0))
    assert own == pytest.approx({"while.1": 3.0, "a": 2.0, "while.2": 3.0,
                                 "b": 1.0, "c": 1.0, "d": 1.0})
    # clipped to the window, the self times still sum to the busy union
    own = dict(B.self_times(ops, 2.0, 12.5))
    assert own["while.1"] == pytest.approx(2.0)
    assert sum(own.values()) == pytest.approx(
        T.covered(T.merge([o[:2] for o in ops]), 2.0, 12.5))


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """Two steps of the tiny yi6b cell traced on the CPU, and the compiled
    step's HLO text.  The CPU has no device plane, so chip 0's ops are laid
    out here, one per scope, inside each step's ``trainer.wait``."""
    c = tiny.cell("yi6b.train")
    run = driver.start(c, jax.devices()[:1])
    driver.prime(run, c, 2**31 + 7)
    logdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(logdir)
    B.steps_timed(run, 2)
    jax.profiler.stop_trace()
    return B.load(logdir), run.trainer.compiled_step.as_text()


def lay_out_ops(tr, hlo):
    """Per wait span: an unscoped op over all of it, with one op of each
    scope nested in it, a tenth of the span long each."""
    named = {}
    for instr, scope in B.scopes_of(hlo).items():
        named.setdefault(scope, instr)
    ops, want = [], {}
    waits = [s for s in tr.spans if s[2] == "trainer.wait"]
    for a, b, _ in waits:
        d = (b - a) / 10
        ops.append((a, b, "not-an-instruction"))
        for i, (scope, instr) in enumerate(sorted(named.items())):
            ops.append((a + i * d, a + (i + 1) * d, instr))
            want[scope] = want.get(scope, 0.0) + d / len(waits)
        want["unscoped"] = want.get("unscoped", 0.0) + (
            (b - a) - len(named) * d) / len(waits)
    tr.ops = {0: ops}
    return want


def test_report_on_a_cpu_made_trace(cpu_trace):
    tr, hlo = cpu_trace
    names = {s[2] for s in tr.spans}
    assert {"bench.window", "bench.step", "trainer.step", "trainer.batch",
            "trainer.put", "trainer.dispatch", "trainer.wait",
            "trainer.readback"} <= names
    assert sum(1 for s in tr.spans if s[2] == "trainer.step") == 2
    want = lay_out_ops(tr, hlo)
    assert {"embed", "norm", "mixer", "ffn", "head", "optimizer"} <= set(want)

    r = B.report(tr, 0, hlo, 2)
    lo, hi = T.window(tr)
    assert r["window_s"] == pytest.approx(hi - lo)
    assert r["scopes"] == pytest.approx(want)
    assert r["self_s"] == pytest.approx(r["busy_s"])
    assert r["unscoped_ops"] == pytest.approx(
        {"not-an-instruction": want["unscoped"]})
    assert r["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"])
    assert sum(r["idle"].values()) == pytest.approx(r["idle_s"])
    assert sum(r["idle_overlap"].values()) == pytest.approx(r["idle_s"])
    # the wait is busy by construction, so no idle lies under it
    assert "trainer.wait" not in r["idle_overlap"]
    assert set(r["idle_overlap"]) <= names
    assert sum(r["bare"].values()) == pytest.approx(sum(
        v for k, v in r["idle"].items() if k.startswith("bench.")))
    # bench.batch lies inside trainer.batch: the program's span names it
    assert "bench.batch" in names and "bench.batch" not in r["idle_overlap"]
