"""One run of one cell: set-up, the measured window, the check.

Set-up builds the program's trainer once, gives it the seed's weights and
tokens, and drives it through the traffic's set-up steps: the first three
are the checked steps that the reference follows, and the widths the
traffic lists warm every mesh and every transition the window will use.
The window then calls ``ElasticTrainer.step()`` back to back for the given
seconds, and fires the traffic's elastic events at step boundaries.  Once
the window has closed and the memory peak is read, the trainer is freed and
the reference runs.
"""
from __future__ import annotations

import gc
import importlib
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import numpy as np

from bench.harness import check, device, program, reference
from bench.harness import trace as trace_mod
from bench.harness.spec import Cell

CHECKED_STEPS = 3


@dataclass
class Record:
    """What a run leaves for the metric readers (``bench/metrics``)."""
    setup_s: float = 0.0
    window_s: float = 0.0
    step_s: List[float] = field(default_factory=list)
    tokens_per_step: int = 0
    flops_per_step: float = 0.0
    events: List[dict] = field(default_factory=list)
    held: List[tuple] = field(default_factory=list)   # (start, end, width)
    peak: Optional[dict] = None
    trace: Optional[trace_mod.Reduced] = None


class Run:
    """The state an elastic event works on."""

    def __init__(self, trainer, devices, span):
        self.trainer, self.devices, self.span = trainer, devices, span


def _step(run: Run) -> dict:
    tr = run.trainer
    with run.span("bench.step"):
        t0 = time.perf_counter()
        m = tr.step()
        jax.block_until_ready((tr.params, tr.opt_state))
        m["seconds"] = time.perf_counter() - t0
    return m


def _rescale(run: Run, width: int) -> dict:
    """Move the job to ``width`` chips, checking its state crosses intact."""
    from bench.events import rescale
    return rescale.fire(run, {"widths": [width]}, 0)


def start(cell: Cell, devices) -> Run:
    """Build the program's trainer on the traffic's first width."""
    tr = program.build_trainer(cell.config, cell.traffic,
                               devices[:cell.traffic["setup_widths"][0]])
    return Run(tr, devices, jax.profiler.TraceAnnotation)


def prime(run: Run, cell: Cell, seed: int):
    """Give the trainer the seed's weights and tokens and take the set-up
    steps.  Returns what the checked steps read, and the state's bit sums
    before and after each set-up rescale."""
    conf, model, tr = cell.config, cell.model, run.trainer
    program.install(tr, model, conf, cell.traffic, seed, run.span)
    prog: Dict[str, object] = {"losses": []}
    crossings = []
    for i, w in enumerate(cell.traffic["setup_widths"]):
        if w != len(tr.devices):
            crossings.append(_rescale(run, w)["intact"])
        m = _step(run)
        if i < CHECKED_STEPS:
            prog["losses"].append(m["loss"])
        if i == 0:
            prog["first_grad"] = program.first_grad_norms(
                tr, conf["optimizer"]["b1"])
        if i == CHECKED_STEPS - 1:
            prog["change"] = program.change_norms(tr, model, conf, seed)
    return prog, crossings


def mismatches(crossings) -> int:
    """Leaves whose bits differ across any of the rescales."""
    return sum(int(np.sum(np.asarray(a) != np.asarray(b)))
               for a, b in crossings)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, devices,
             t_start: float, peaks: Optional[dict] = None) -> dict:
    conf, traffic, model = cell.config, cell.traffic, cell.model
    t_import = time.perf_counter()
    run = start(cell, devices)
    t_built = time.perf_counter()
    tr, span = run.trainer, run.span
    prog, crossings = prime(run, cell, seed)
    print(f"setup: imports and device check {t_import - t_start} s, trainer "
          f"build (init + compile) {t_built - t_import} s, weights and "
          f"set-up steps {time.perf_counter() - t_built} s", file=sys.stderr)

    rec = Record(peak=peaks,
                 tokens_per_step=traffic["global_batch"] * traffic["seq_len"],
                 flops_per_step=model.step_flops(
                     conf, traffic["global_batch"], traffic["seq_len"]))
    specs = traffic.get("events", [])
    modules = [importlib.import_module(f"bench.events.{s['kind']}")
               for s in specs]
    due = [s["every_s"] for s in specs]
    fired = [0] * len(specs)
    logdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    if traced:
        jax.profiler.start_trace(logdir)
    rec.setup_s = time.perf_counter() - t_start
    failed, width, t_w = 0, len(tr.devices), 0.0
    with span("bench.window"):
        t0 = time.perf_counter()
        while (now := time.perf_counter() - t0) < seconds:
            for j, (mod, s) in enumerate(zip(modules, specs)):
                if now >= due[j]:
                    ev = mod.fire(run, s, fired[j])
                    fired[j] += 1
                    due[j] = s["every_s"] * (math.floor(now / s["every_s"]) + 1)
                    ev["start"] -= t0
                    ev["end"] -= t0
                    now_w = len(run.trainer.devices)
                    rec.held += [(t_w, ev["start"], width),
                                 (ev["start"], ev["end"], max(width, now_w))]
                    t_w, width = ev["end"], now_w
                    rec.events.append(ev)
            m = _step(run)
            rec.step_s.append(m["seconds"])
            failed += not math.isfinite(m["loss"])
        rec.window_s = time.perf_counter() - t0
    rec.held.append((t_w, rec.window_s, width))
    if traced:
        jax.profiler.stop_trace()
    crossings += [ev["intact"] for ev in rec.events if "intact" in ev]
    failed += sum(1 for c in crossings if mismatches([c]))

    dev_info = device.describe(devices)
    dev_info["memory_peak_bytes"] = device.memory_peak(devices)
    del tr, run, m
    gc.collect()

    breakdown = None
    if traced:
        tr_ = trace_mod.load(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
        lo, _ = trace_mod.window(tr_)
        ids = [d.id for d in devices]
        rec.trace = trace_mod.reduce(
            tr_, [(lo + a, lo + b, ids[:w]) for a, b, w in rec.held])
        busy = [rec.trace.busy_s.get(i, 0.0) for i in
                ids[:max(w for _, _, w in rec.held)]]
        dev_info["busy_s"] = sum(busy) / len(busy)
        dev_info["window_s"] = rec.trace.window_s
        breakdown = {"device_ops": [list(x) for x in rec.trace.top_ops],
                     "idle_gaps": [list(x) for x in rec.trace.idle_gaps]}

    ref = reference.run(model, conf, traffic, seed, CHECKED_STEPS)
    nums = check.numbers(prog, ref)
    if crossings:
        nums["state_mismatch"] = float(mismatches(crossings))
    ok, checks = check.verdict(nums, cell.limits)

    entries = cell.per_layer if traced else cell.end_to_end
    metrics = {}
    for e in entries:
        value = importlib.import_module(f"bench.metrics.{e['name']}").read(rec)
        if value is not None:
            metrics[e["name"]] = {"value": value, "unit": e["unit"]}
    result = {"correct": ok and failed == 0,
              "attempted": len(rec.step_s) + len(rec.events),
              "failed": failed, "metrics": metrics, "device": dev_info}
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
