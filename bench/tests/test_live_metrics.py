"""The readers of the program's own spans, on a hand-built span record:
they read the window's steps alone, and nothing where there is nothing."""
import importlib
import os
import sys

import pytest

from bench.harness.driver import Record
from bench.harness.spec import ROOT

sys.path.insert(0, os.path.join(ROOT, "src"))
from repro import obs  # noqa: E402
from repro.obs import live  # noqa: E402
from repro.obs.live import SpanRecord  # noqa: E402

SETUP, WINDOW = 4, 5        # set-up steps 0-3, window steps 4-8


def reader(name):
    return importlib.import_module(f"bench.metrics.{name}").read


def hand_built() -> dict:
    """Step k's spans last k + 1 ms each (batch, put, dispatch); set-up
    steps 1 s, so a reader that took them would be far off.  Build spans:
    two compiles (the build and a rescale) and one init."""
    rec, t = {n: [] for n in ("trainer.step", "trainer.batch", "trainer.put",
                              "trainer.dispatch", "trainer.readback")}, 0.0
    for k in range(SETUP + WINDOW):
        d = 1.0 if k < SETUP else (k + 1) * 1e-3
        rec["trainer.step"].append(SpanRecord(t, t + 5 * d, k, None))
        for i, n in enumerate(("trainer.batch", "trainer.put",
                               "trainer.dispatch", "trainer.readback")):
            rec[n].append(SpanRecord(t + i * d, t + (i + 1) * d, k,
                                     "trainer.step"))
        t += 5 * d
    rec["trainer.compile"] = [SpanRecord(0.0, 2.5, None, "trainer.build"),
                              SpanRecord(9.0, 9.25, 7, "elastic.restart")]
    rec["trainer.init"] = [SpanRecord(0.0, 0.75, None, "trainer.build")]
    return rec


@pytest.fixture
def spans(monkeypatch):
    rec = hand_built()
    monkeypatch.setattr(live, "spans", lambda name: list(rec.get(name, ())))
    return rec


def window_record() -> Record:
    return Record(step_s=[0.3] * WINDOW)


@pytest.mark.parametrize("name,want", [
    # window steps 4..8 take 5..9 ms a span: median at step 6, 7 ms
    ("step_host_s", 3 * 7e-3),
    ("readback_s", 7e-3),
    ("compile_s", 2.75),
    ("init_s", 0.75),
])
def test_reader_on_hand_built_spans(spans, name, want):
    assert reader(name)(window_record()) == pytest.approx(want)


def test_window_takes_only_the_last_steps(spans):
    from bench.metrics import _spans
    assert _spans.window_steps(window_record()) == [4, 5, 6, 7, 8]
    assert _spans.window_steps(Record(step_s=[0.3])) == [8]
    assert reader("readback_s")(Record(step_s=[0.3])) == pytest.approx(9e-3)


@pytest.mark.parametrize("name", ["step_host_s", "readback_s", "compile_s",
                                  "init_s"])
def test_reader_gives_none_without_spans(monkeypatch, name):
    monkeypatch.setattr(live, "spans", lambda name: [])
    assert reader(name)(window_record()) is None
    # a program without repro.obs.live, as before it had one
    monkeypatch.delattr(obs, "live")
    monkeypatch.setitem(sys.modules, "repro.obs.live", None)
    assert reader(name)(window_record()) is None


def test_window_readers_need_window_steps(spans):
    assert reader("step_host_s")(Record()) is None
    assert reader("readback_s")(Record()) is None
