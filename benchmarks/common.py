"""Shared benchmark utilities: CSV emission per the harness contract
(``name,us_per_call,derived``)."""
import json
import os
import subprocess
import sys
import time

# label of rows measured in a child on 8 virtual CPU devices
CPU8 = "device=cpu:8"


def run_cpu_helper(code: str, timeout: int):
    """Run ``code`` in a child Python on 8 virtual CPU devices and return
    the JSON it prints after ``JSON``; raise if the child fails.

    The child models an 8-device cluster and is held to the CPU: a chip
    belongs to one process, and on a machine with one that is not a child
    of this harness."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath("src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout, env=env)
    for line in proc.stdout.splitlines():
        if line.startswith("JSON"):
            return json.loads(line[4:])
    raise RuntimeError(f"helper failed (rc={proc.returncode}): "
                       f"{proc.stderr[-400:]}")


def emit(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.3f},{derived}")
    sys.stdout.flush()


def kv(*fragments: str, **fields) -> str:
    """Build a derived-field string: ``k=v;...``.  Floats render compactly;
    string ``fragments`` (e.g. a WorkloadStats.kv()) are spliced in as-is so
    characterization columns ride along with metric columns."""
    parts = [f for f in fragments if f]
    for k, v in fields.items():
        parts.append(f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}")
    return ";".join(parts)


def flat_metrics(m) -> dict:
    """Flatten ``ScheduleMetrics.to_dict()``: dict-valued fields become
    dotted keys (``percentiles.resp_p99``, ``counters.events``)."""
    out = {}
    for k, v in m.to_dict().items():
        if isinstance(v, dict):
            for k2, v2 in v.items():
                out[f"{k}.{k2}"] = v2
        else:
            out[k] = v
    return out


def metrics_kv(m, *keys, prefixes=(), **extra) -> str:
    """Derived-field string straight from a :class:`ScheduleMetrics`:
    ``keys`` name flat fields to emit (missing keys are skipped — a
    fixed-capacity run has no ``percentiles.resp_p99_prio5`` until a
    priority-5 job completes); ``prefixes`` pull every flat key under a
    dotted prefix (e.g. ``percentiles.resp_p99`` matches the aggregate and
    each priority class).  Output names drop the dict-field prefix."""
    flat = flat_metrics(m)
    fields = {}
    for k in keys:
        if k in flat:
            fields[k.split(".", 1)[-1]] = flat[k]
    for p in prefixes:
        for k in sorted(flat):
            if k.startswith(p):
                fields[k.split(".", 1)[-1]] = flat[k]
    fields.update(extra)
    return kv(**fields)


def phases_kv(cells) -> str:
    """Derived-field string of mean per-phase seconds (the priority-weighted
    makespan decomposition from ``repro.obs.critical_path``) over one or more
    :class:`ScheduleMetrics` — the ``.phases`` row every table emits next to
    its headline numbers.  Empty string when no cell carries phases."""
    ms = cells if isinstance(cells, (list, tuple)) else [cells]
    ms = [m for m in ms if getattr(m, "phase_seconds", None)]
    if not ms:
        return ""
    acc = {}
    for m in ms:
        for k, v in m.phase_seconds.items():
            acc[k] = acc.get(k, 0.0) + v
    n = len(ms)
    return kv(**{k: v / n for k, v in acc.items()})


def time_call(fn, *args, repeat: int = 3, **kw):
    """Median wall time in microseconds."""
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn(*args, **kw)
        ts.append((time.perf_counter() - t0) * 1e6)
    ts.sort()
    return ts[len(ts) // 2]
