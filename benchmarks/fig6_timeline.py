"""Paper Fig. 6 — per-iteration timeline across a shrink and an expand.

Real run on 8 virtual CPU devices (rows labelled ``device=cpu:8``):
iteration times rise after shrink, fall after expand; the rescale gaps are
the measured overheads.
"""
import sys

from benchmarks.common import CPU8, emit, run_cpu_helper

HELPER = r"""
import json, time
import jax
from repro.configs import smoke_config
from repro.core.elastic import ElasticTrainer, TrainJobConfig

devs = jax.devices()
cfg = smoke_config("yi-6b").with_(d_model=128, num_layers=4, expected_params=0.0)
tr = ElasticTrainer(cfg, TrainJobConfig(global_batch=8, seq_len=64,
                                        total_steps=30, seed=0), devs[:4])
events = []
def run_steps(n):
    for _ in range(n):
        t0 = time.perf_counter()
        tr.step()
        events.append(("step", tr.replicas, time.perf_counter() - t0))
run_steps(8)
t = tr.rescale(devs[:2])
events.append(("shrink", 2, t.total))
run_steps(8)
t = tr.rescale(devs[:4])
events.append(("expand", 4, t.total))
run_steps(8)
print("JSON" + json.dumps(events))
"""


def run():
    events = run_cpu_helper(HELPER, timeout=1800)
    phase, buf = 0, []
    for kind, replicas, dt in events:
        if kind == "step":
            buf.append(dt)
        else:
            emit(f"fig6.phase{phase}.steps.r{buf and len(buf)}",
                 1e6 * sum(buf) / len(buf),
                 f"replicas_before={replicas};{CPU8}")
            emit(f"fig6.{kind}", dt * 1e6, f"to_replicas={replicas};{CPU8}")
            phase += 1
            buf = []
    if buf:
        emit(f"fig6.phase{phase}.steps", 1e6 * sum(buf) / len(buf), CPU8)
    # render the measured run as a flight-recorder timeline (stderr keeps
    # the stdout CSV clean); the trace records mirror what a live tracer
    # would have emitted for this one-job shrink/expand story
    print(_timeline(events), file=sys.stderr)


def _timeline(events) -> str:
    """Rebuild trace records from the helper's (kind, replicas, dt) events
    and render them with the shared Gantt renderer."""
    from repro.obs.timeline import render
    records = [{"kind": "run_start", "t": 0.0, "run": 1, "slots": 4},
               {"kind": "job_start", "t": 0.0, "job": "fig6-job",
                "slots": 4, "priority": 1, "resume": False}]
    t, replicas = 0.0, 4
    for kind, to_replicas, dt in events:
        t += dt
        if kind in ("shrink", "expand"):
            records.append({"kind": "job_rescale", "t": t, "job": "fig6-job",
                            "from": replicas, "to": to_replicas,
                            "overhead_s": dt})
            replicas = to_replicas
    records.append({"kind": "job_complete", "t": t, "job": "fig6-job",
                    "slots": replicas})
    records.append({"kind": "run_end", "t": t, "run": 1})
    return render(records, width=60)
