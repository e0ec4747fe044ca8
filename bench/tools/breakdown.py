#!/usr/bin/env python3
"""Where a cell's steps spend their time, by program span and by model scope.

    python3 bench/tools/breakdown.py --workload yi6b.train --seed 7 \
        --steps 30 --out breakdown.json

Takes the cell's set-up as a benchmark run does (``driver.start``,
``driver.prime``), times ``--steps`` steps (``driver._step``) untraced, then
traces as many more, and prints one JSON object:

- ``tokens_per_s``: the untraced and the traced steps' rate, the cost of
  tracing when it is on;
- ``idle``: chip 0's idle seconds in the traced window by the innermost
  of the program's ``trainer.*`` and ``elastic.*`` host spans open at each
  gap's midpoint, or where none is, the benchmark's innermost ``bench.*``
  span; ``idle_overlap`` splits the same seconds by the span open at each
  instant; ``bare`` names, for the gaps under no program span, the program
  spans they lie between;
- ``scopes``: chip 0's device self time per step by the model step's named
  scope.  An op's self time is its duration less that of the ops nested in
  it on the same line; it goes to the innermost scope named in the
  ``op_name`` that the compiled step's HLO text gives the instruction of
  that name.  Ops that match no scope are ``unscoped``, and
  ``unscoped_ops`` names the largest.

Only elastic-event-free steps are traced.  The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench.harness import trace as trace_mod  # noqa: E402

SPAN_PREFIXES = ("bench.", "trainer.", "elastic.")
SCOPES = ("embed", "norm", "mixer", "ffn", "experts", "head", "optimizer")
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s.*?op_name="([^"]*)"')
_WORD = re.compile(r"[\w.]+")

Op = Tuple[float, float, str]


def load(logdir: str) -> trace_mod.Trace:
    """The trace's device ops (``trace.load``) with every host span of the
    benchmark and the program."""
    import jax
    tr = trace_mod.load(logdir)
    path = max(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(path)
    tr.spans = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                 e.name)
                for plane in data.planes if plane.name.startswith("/host:")
                for line in plane.lines for e in line.events
                if e.name.startswith(SPAN_PREFIXES)]
    return tr


def scopes_of(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> the innermost of ``SCOPES`` in its ``op_name``,
    for each instruction that has one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            words = [w for w in _WORD.findall(m.group(2)) if w in SCOPES]
            if words:
                out[m.group(1)] = words[-1]
    return out


def self_times(ops: Sequence[Op], lo: float, hi: float) -> List[Tuple[str, float]]:
    """Each op's seconds inside ``[lo, hi]`` less those of the ops nested
    directly inside it (ops on one line nest or follow each other)."""
    clipped = sorted(((max(a, lo), min(b, hi), n) for a, b, n in ops
                      if b > lo and a < hi), key=lambda o: (o[0], -o[1]))
    own = [b - a for a, b, _ in clipped]
    stack: List[int] = []
    for i, (a, b, _) in enumerate(clipped):
        while stack and clipped[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(b, clipped[stack[-1]][1]) - a
        stack.append(i)
    return [(n, s) for (_, _, n), s in zip(clipped, own)]


def idle(tr: trace_mod.Trace, chip: int, lo: float, hi: float) -> dict:
    """Chip ``chip``'s idle seconds in ``[lo, hi]`` by the innermost program
    span, or where none is open the innermost ``bench.*`` span: at each
    gap's midpoint (``idle``) and at each instant (``idle_overlap``); and,
    for gaps under no program span, the program spans each lies between
    (``bare``)."""
    order = lambda ss: sorted(ss, key=lambda s: (s[0], -s[1]))
    prog = order(s for s in tr.spans if not s[2].startswith("bench."))
    groups = [(ss, [s[0] for s in ss]) for ss in
              (prog, order(s for s in tr.spans if s[2].startswith("bench.")))]

    def innermost(t: float) -> str:
        for ss, starts in groups:
            name = trace_mod.span_at(ss, starts, t)
            if name != "no span":
                return name
        return "no span"

    by_mid: Dict[str, float] = defaultdict(float)
    by_overlap: Dict[str, float] = defaultdict(float)
    bare: Dict[str, float] = defaultdict(float)
    gaps = trace_mod.gaps(trace_mod.merge([(a, b) for a, b, _ in
                                           tr.ops.get(chip, [])]), lo, hi)
    for a, b in gaps:
        mid = (a + b) / 2
        name = innermost(mid)
        by_mid[name] += b - a
        cuts = sorted({a, b, *(t for s in tr.spans for t in s[:2]
                               if a < t < b)})
        for x, y in zip(cuts, cuts[1:]):
            by_overlap[innermost((x + y) / 2)] += y - x
        if not name.startswith(("trainer.", "elastic.")):
            before = max((s for s in prog if s[1] <= mid),
                         key=lambda s: s[1], default=(0, 0, "nothing"))
            after = min((s for s in prog if s[0] >= mid),
                        key=lambda s: s[0], default=(0, 0, "nothing"))
            bare[f"{name} after {before[2]}, before {after[2]}"] += b - a
    rank = lambda d: dict(sorted(d.items(), key=lambda kv: -kv[1]))
    return {"idle_s": sum(b - a for a, b in gaps), "idle": rank(by_mid),
            "idle_overlap": rank(by_overlap), "bare": rank(bare)}


def scopes(tr: trace_mod.Trace, chip: int, lo: float, hi: float,
           hlo_text: str, steps: int, top: int = 10) -> dict:
    """Chip ``chip``'s device self time per step by named scope."""
    scope = scopes_of(hlo_text)
    per: Dict[str, float] = defaultdict(float)
    unscoped: Dict[str, float] = defaultdict(float)
    for name, s in self_times(tr.ops.get(chip, []), lo, hi):
        per[scope.get(name, "unscoped")] += s / steps
        if name not in scope:
            unscoped[name] += s / steps
    busy = trace_mod.covered(trace_mod.merge(
        [(a, b) for a, b, _ in tr.ops.get(chip, [])]), lo, hi)
    return {"busy_s": busy, "self_s": sum(per.values()) * steps,
            "scopes": dict(sorted(per.items(), key=lambda kv: -kv[1])),
            "unscoped_ops": dict(sorted(unscoped.items(),
                                        key=lambda kv: -kv[1])[:top])}


def report(tr: trace_mod.Trace, chip: int, hlo_text: str, steps: int) -> dict:
    lo, hi = trace_mod.window(tr)
    return {"window_s": hi - lo, **idle(tr, chip, lo, hi),
            **scopes(tr, chip, lo, hi, hlo_text, steps)}


def steps_timed(run, n: int) -> float:
    """Seconds of ``n`` steps, back to back, inside one ``bench.window``."""
    from bench.harness import driver
    with run.span("bench.window"):
        t0 = time.perf_counter()
        for _ in range(n):
            driver._step(run)
        return time.perf_counter() - t0


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--out", help="also write the JSON object here")
    args = ap.parse_args()

    import jax
    from bench.harness import device, driver, spec
    from bench.run import CACHE_DIR
    cell = spec.resolve(args.workload)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devs = device.require_chips(cell.chips)[:cell.chips]
    except device.NoChip as e:
        sys.exit(str(e))
    run = driver.start(cell, devs)
    driver.prime(run, cell, args.seed)
    tokens = args.steps * cell.traffic["global_batch"] * cell.traffic["seq_len"]
    untraced = steps_timed(run, args.steps)
    logdir = tempfile.mkdtemp(prefix="bench_breakdown_")
    jax.profiler.start_trace(logdir)
    traced = steps_timed(run, args.steps)
    jax.profiler.stop_trace()
    out = {"workload": cell.name, "seed": args.seed, "steps": args.steps,
           "device": device.describe(devs),
           "tokens_per_s": {"untraced": tokens / untraced,
                            "traced": tokens / traced}}
    out.update(report(load(logdir), devs[0].id,
                      run.trainer.compiled_step.as_text(), args.steps))
    shutil.rmtree(logdir, ignore_errors=True)
    text = json.dumps(out, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)


if __name__ == "__main__":
    main()
