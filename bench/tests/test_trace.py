"""The trace reduction: busy union, idle share, top ops, and idle gaps named
by the benchmark's host spans."""
import glob
import os

import pytest

from bench.harness import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))


def small_trace() -> T.Trace:
    return T.Trace(
        ops={0: [(0.0, 0.3, "fusion.a"), (0.31, 0.6, "fusion.b"),
                 (0.62, 0.9, "fusion.a"), (0.1, 0.2, "fusion.b")],
             1: [(0.5, 0.7, "fusion.c")]},
        spans=[(0.0, 1.0, "bench.window"), (0.0, 0.3, "bench.step"),
               (0.3, 0.6, "bench.step"), (0.6, 0.9, "bench.step"),
               (0.6, 0.63, "bench.batch"), (0.92, 1.0, "bench.rescale")])


def test_merge_and_gaps():
    m = T.merge([(0.1, 0.2), (0.0, 0.3), (0.31, 0.6), (0.5, 0.55)])
    assert m == [(0.0, 0.3), (0.31, 0.6)]
    assert T.covered(m, 0.2, 0.4) == pytest.approx(0.19)
    assert T.gaps(m, 0.0, 1.0) == [(0.3, 0.31), (0.6, 1.0)]


def test_reduce_small_trace():
    r = T.reduce(small_trace(), [(0.0, 0.5, [0]), (0.5, 1.0, [0, 1])])
    assert r.window_s == pytest.approx(1.0)
    assert r.busy_s[0] == pytest.approx(0.87)      # overlap counted once
    assert r.busy_s[1] == pytest.approx(0.2)
    # chip 0 held all window, chip 1 its second half
    assert r.held_s == pytest.approx(1.5)
    assert r.held_busy_s == pytest.approx(0.49 + 0.38 + 0.2)
    assert r.idle_share == pytest.approx(1 - 1.07 / 1.5)
    ops = dict(r.top_ops)
    assert ops["fusion.a"] == pytest.approx((0.3 + 0.28) / 2)
    assert ops["fusion.b"] == pytest.approx((0.29 + 0.1) / 2)
    assert [n for n, _ in r.top_ops] == ["fusion.a", "fusion.b", "fusion.c"]
    gaps = dict(r.idle_gaps)
    assert gaps == pytest.approx({"bench.step": 0.01, "bench.batch": 0.02,
                                  "bench.rescale": 0.1})
    assert r.idle_gaps[0][0] == "bench.rescale"


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = T.load(str(tmp_path))
    names = [s[2] for s in tr.spans]
    assert names.count("bench.step") == 2 and "bench.window" in names
    lo, hi = T.window(tr)
    assert all(lo <= a <= b <= hi for a, b, n in tr.spans if n == "bench.step")


RECORDED = glob.glob(os.path.join(HERE, "data", "*.xplane.pb"))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_reduce_recorded_tpu_trace(path, tmp_path):
    """A trace recorded on one v5e chip: three steps of a 4096 x 4096 f32
    matmul, each inside ``bench.step`` with a 10 ms ``bench.batch`` sleep
    after it, all inside ``bench.window``."""
    os.makedirs(tmp_path / "plugins" / "profile" / "run")
    dst = tmp_path / "plugins" / "profile" / "run" / os.path.basename(path)
    dst.write_bytes(open(path, "rb").read())
    tr = T.load(str(tmp_path))
    assert list(tr.ops) == [0]
    lo, hi = T.window(tr)
    r = T.reduce(tr, [(lo, hi, [0])])
    steps = [s for s in tr.spans if s[2] == "bench.step"]
    assert len(steps) == 3
    # three matmuls of 2 * 4096**3 FLOPs: busy well under the window
    assert 0 < r.busy_s[0] < 0.2 * r.window_s
    assert 0.8 < r.idle_share < 1.0
    assert r.top_ops[0][1] == max(v for _, v in r.top_ops)
    assert dict(r.idle_gaps).get("bench.batch", 0) > 0.02
