"""Paper Table 1 — the four policies on one job set: simulation AND an
"actual" run (the live controller with real JAX training jobs on virtual
devices — the EKS analog this container can execute honestly).

The live run uses 8 slots and tiny jobs; absolute numbers differ from the
64-vCPU EKS cluster, but the table's *orderings* are the reproduction target
(DESIGN.md §6.5).
"""
import numpy as np

from benchmarks.common import CPU8, emit, metrics_kv, run_cpu_helper

LIVE_HELPER = r"""
import json, math
import jax
from repro.configs import smoke_config
from repro.core import (ElasticClusterController, ElasticTrainer, JobSpec,
                        PolicyConfig, TrainJobConfig)

devs = jax.devices()

JOBS = [  # (id, priority, min, max, submit_tick, steps)
    ("j0", 3, 2, 8, 0.000, 12),
    ("j1", 5, 2, 4, 0.001, 8),
    ("j2", 1, 2, 8, 0.002, 10),
    ("j3", 4, 4, 8, 0.003, 8),
    ("j4", 2, 2, 4, 0.004, 8),
]

def factory(steps, seed):
    def f(devices):
        return ElasticTrainer(smoke_config("yi-6b"),
                              TrainJobConfig(global_batch=8, seq_len=16,
                                             total_steps=steps, seed=seed),
                              devices)
    return f

def run(variant):
    gap = 0.0 if variant in ("elastic",) else (math.inf if variant == "moldable" else 0.0)
    op = ElasticClusterController(devs, slots=8,
                                  policy=PolicyConfig(rescale_gap=gap),
                                  steps_per_tick=2)
    for i, (jid, prio, mn, mx, sub, steps) in enumerate(JOBS):
        if variant == "rigid_min":
            mn2 = mx2 = mn
        elif variant == "rigid_max":
            mn2 = mx2 = mx
        else:
            mn2, mx2 = mn, mx
        op.submit(JobSpec(jid, prio, mn2, mx2, sub, divides=8),
                  factory(steps, i))
    m = op.run()
    return dict(total=m.total_time, util=m.utilization,
                resp=m.weighted_mean_response,
                compl=m.weighted_mean_completion,
                rescales=m.rescale_count, dropped=m.dropped_jobs)

out = {v: run(v) for v in ("rigid_min", "rigid_max", "moldable", "elastic")}
print("JSON" + json.dumps(out))
"""


def run():
    import time

    from repro.core.simulator import VARIANTS, make_jacobi_jobs, run_variant

    # --- simulation columns (paper setup: gap 90 s, T_gap 180 s) ------------
    specs = make_jacobi_jobs(seed=7, n_jobs=16, submission_gap=90.0)
    for v in VARIANTS:
        t0 = time.perf_counter()
        m = run_variant(v, specs, total_slots=64, rescale_gap=180.0)
        us = (time.perf_counter() - t0) * 1e6
        # machine-readable row off ScheduleMetrics.to_dict(); the resp_p99
        # prefix pulls the aggregate AND per-priority-class p99 response,
        # the phase_seconds prefix the per-phase makespan decomposition
        # counters.stale_events rides along: rescale-heavy variants show how
        # much dead weight (invalidated completions) the event heap carried
        emit(f"table1.sim.{v}", us, metrics_kv(
            m, "total_time", "utilization", "weighted_mean_response",
            "weighted_mean_completion", "rescale_count",
            "counters.events", "counters.stale_events",
            prefixes=("percentiles.resp_p99", "phase_seconds.")))

    # --- "actual" columns: live controller with real training jobs ----------
    for v, m in run_cpu_helper(LIVE_HELPER, timeout=3600).items():
        emit(f"table1.live.{v}", m["total"] * 1e6,
             f"util={m['util']:.3f};resp={m['resp']:.2f};"
             f"compl={m['compl']:.2f};rescales={m['rescales']};"
             f"dropped={m['dropped']};{CPU8}")
