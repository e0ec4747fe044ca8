"""Live spans and named scopes of the elastic trainer
(``repro.obs.live``), on a tiny dense config on the CPU."""
import glob
import os
import re

import jax
import pytest

from repro.configs import smoke_config
from repro.core.elastic import ElasticTrainer, TrainJobConfig
from repro.obs import live

CHILDREN = ("trainer.batch", "trainer.put", "trainer.dispatch",
            "trainer.wait", "trainer.readback")
SCOPES = ("embed", "norm", "mixer", "ffn", "head", "optimizer")
METRICS = 6       # loss, xent, aux, tokens, grad_norm, lr


@pytest.fixture(autouse=True)
def fresh_record():
    live.reset()
    yield
    live.reset()


def trainer() -> ElasticTrainer:
    return ElasticTrainer(smoke_config("yi-6b"),
                          TrainJobConfig(global_batch=2, seq_len=16,
                                         total_steps=10),
                          jax.devices()[:1])


def test_span_records_parent_step_and_nesting():
    with live.span("outer", step=7):
        with live.span("inner") as inner:
            pass
    with live.span("inner", step=3):
        pass
    (outer,) = live.spans("outer")
    first, second = live.spans("inner")
    assert first == inner.record
    assert (first.parent, first.step) == ("outer", 7)
    assert (second.parent, second.step) == (None, 3)
    assert outer.start <= first.start <= first.end <= outer.end
    assert outer.parent is None and live.spans("absent") == []


def test_record_is_bounded_and_reset_clears_it():
    for _ in range(live.MAXLEN + 5):
        with live.span("many"):
            pass
    assert len(live.spans("many")) == live.MAXLEN
    live.reset()
    assert live.spans("many") == []


def test_each_step_nests_its_five_children():
    tr = trainer()
    for _ in range(3):
        tr.step()
    steps = live.spans("trainer.step")
    assert [s.step for s in steps] == [0, 1, 2]
    for name in CHILDREN:
        kids = live.spans(name)
        assert [k.step for k in kids] == [0, 1, 2], name
        for k, s in zip(kids, steps):
            assert k.parent == "trainer.step"
            assert s.start <= k.start <= k.end <= s.end
    # the children run in order, each after the one before
    for i in range(3):
        ends = [live.spans(n)[i] for n in CHILDREN]
        assert all(a.end <= b.start for a, b in zip(ends, ends[1:]))


def test_one_compile_after_build_and_one_readback_a_step():
    tr = trainer()
    assert len(live.spans("trainer.compile")) == 1
    assert live.spans("trainer.step") == []
    for _ in range(2):
        tr.step()
    assert len(live.spans("trainer.readback")) == 2
    # each readback brings the step's metrics back as Python floats
    for m in tr.metrics_log:
        assert len(m) == METRICS + 2          # + step, replicas
        assert all(type(m[k]) is float for k in m
                   if k not in ("step", "replicas"))
    assert len(live.spans("trainer.compile")) == 1


def test_build_spans_and_startup_time():
    tr = trainer()
    (build,) = live.spans("trainer.build")
    (init,) = live.spans("trainer.init")
    (comp,) = live.spans("trainer.compile")
    assert tr.startup_time == build.seconds
    for child in (init, comp):
        assert child.parent == "trainer.build"
        assert build.start <= child.start <= child.end <= build.end
    assert init.end <= comp.start


def test_revisited_mesh_is_a_cache_hit_not_a_compile():
    tr = trainer()
    tr.step()
    tr.rescale(jax.devices()[:1])
    (restart,) = live.spans("elastic.restart")
    (compile_,) = live.spans("trainer.compile")
    assert compile_.parent == "trainer.build"
    assert compile_.end <= restart.start


@pytest.mark.parametrize("via_host", [True, False])
def test_rescale_timings_are_the_span_durations(via_host):
    tr = trainer()
    tr.step()
    tr.step()
    t = tr.rescale(jax.devices()[:1], via_host=via_host)
    (whole,) = live.spans("elastic.rescale")
    assert whole.step == 2
    stages = ["load_balance", "restart", "restore"]
    if via_host:
        stages.append("checkpoint")
    else:
        assert t.checkpoint == 0.0 and live.spans("elastic.checkpoint") == []
    for stage in stages:
        (sp,) = live.spans(f"elastic.{stage}")
        assert getattr(t, stage) == sp.seconds
        assert (sp.parent, sp.step) == ("elastic.rescale", 2)
        assert whole.start <= sp.start <= sp.end <= whole.end
    assert t.total == pytest.approx(sum(
        live.spans(f"elastic.{s}")[0].seconds for s in stages))


def test_profile_host_plane_holds_the_trainer_spans(tmp_path):
    tr = trainer()
    tr.step()                       # the profile holds no compile
    with jax.profiler.trace(str(tmp_path)):
        tr.step()
        tr.rescale(jax.devices()[:1])
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    wanted = {"trainer.step", *CHILDREN, "elastic.rescale",
              "elastic.load_balance", "elastic.restart", "elastic.restore"}
    assert wanted <= names


def test_compiled_step_carries_each_scope_in_op_names():
    tr = trainer()
    op_names = re.findall(r'op_name="([^"]*)"', tr.compiled_step.as_text())
    components = {c for n in op_names for c in re.findall(r"[\w.]+", n)}
    for scope in SCOPES:
        assert scope in components, scope
    # backward ops keep the scope of the forward op they differentiate
    backward = [set(re.findall(r"[\w.]+", n)) for n in op_names
                if "transpose(jvp(" in n]
    for scope in ("mixer", "ffn", "norm", "head"):
        assert any(scope in c for c in backward), scope
