"""A whole run, past the look for a chip, with the timed path broken
underneath: ``correct`` must come out false for each fault a cell can have.
A sound run of each cell comes out true."""
import time

import jax
import pytest

from bench.harness import driver, program
from bench.tests import tiny

SEED = 2**31 + 12345


def run(name: str) -> dict:
    c = tiny.cell(name)
    return driver.run_cell(c, SEED, 0.5, False, jax.devices()[:c.chips],
                           time.perf_counter())


def trainer_class():
    return program._program()[1]


@pytest.mark.parametrize("name", ["yi6b.train", "mamba2.train",
                                  "yi6b.rescale4"])
def test_sound_run_is_correct(name):
    r = run(name)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


def test_state_left_unchanged_is_not_correct(monkeypatch):
    cls = trainer_class()
    step = cls._step_fn

    def unchanged(self, params, opt_state, batch, s):
        _, _, metrics = step(self, params, opt_state, batch, s)
        return params, opt_state, metrics
    monkeypatch.setattr(cls, "_step_fn", unchanged)
    r = run("yi6b.train")
    assert not r["correct"]
    assert r["checks"]["change_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


@pytest.mark.parametrize("name", ["yi6b.train", "mamba2.train"])
def test_half_batch_left_out_is_not_correct(monkeypatch, name):
    cls = trainer_class()
    step = cls._step_fn

    def half(self, params, opt_state, batch, s):
        rows = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return step(self, params, opt_state, rows, s)
    monkeypatch.setattr(cls, "_step_fn", half)
    assert not run(name)["correct"]


def test_exchange_between_chips_left_out_is_not_correct(monkeypatch):
    """Each replica steps on its own rows; the state read is the first's."""
    cls = trainer_class()
    step = cls._step_fn

    def local(self, params, opt_state, batch, s):
        rows = {k: v[:v.shape[0] // self.replicas] for k, v in batch.items()}
        return step(self, params, opt_state, rows, s)
    monkeypatch.setattr(cls, "_step_fn", local)
    assert not run("yi6b.rescale4")["correct"]


def test_state_altered_across_a_rescale_is_not_correct(monkeypatch):
    cls = trainer_class()
    rescale = cls.rescale

    def altered(self, devices, **kw):
        t = rescale(self, devices, **kw)
        self.params = jax.tree.map(lambda x: x, self.params)
        leaf = self.params["final_norm"]
        self.params["final_norm"] = leaf.at[0].add(1e-3)
        return t
    monkeypatch.setattr(cls, "rescale", altered)
    r = run("yi6b.rescale4")
    assert not r["correct"]
    assert r["checks"]["state_mismatch"]["value"] > 0
