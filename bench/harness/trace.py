"""Reduce a profiler trace to device busy time, idle share and its causes.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: for each
chip, the intervals of the operations it ran (the ``XLA Ops`` line of the
``/device:TPU:<n>`` plane), and the host spans that the benchmark records
(``bench.*`` annotations).  Everything else is plain interval arithmetic on
those lists, so it can be checked on a small recorded trace.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]           # seconds on the trace's clock
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclass
class Trace:
    ops: Dict[int, List[Tuple[float, float, str]]] = field(default_factory=dict)
    spans: List[Tuple[float, float, str]] = field(default_factory=list)


def load(logdir: str) -> Trace:
    import jax
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    tr = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1])
            tr.ops[chip] = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                             * 1e-9, op_name(e.name))
                            for line in plane.lines if line.name == OPS_LINE
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            tr.spans += [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                          * 1e-9, e.name)
                         for line in plane.lines for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
    return tr


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def covered(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` that the merged intervals cover."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for a, b in merged:
        if b <= lo or a >= hi:
            continue
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def span_at(spans: Sequence[Tuple[float, float, str]], starts: Sequence[float],
            t: float) -> str:
    """The innermost host span open at ``t``: the latest to start of those
    that contain it.  ``spans`` sorted by start, then by end descending;
    ``starts`` their starts."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if spans[i][1] >= t:
            return spans[i][2]
    return "no span"


def window(tr: Trace, name: str = "bench.window") -> Interval:
    lo, hi, _ = next(s for s in tr.spans if s[2] == name)
    return lo, hi


@dataclass
class Reduced:
    window_s: float
    busy_s: Dict[int, float]             # per chip, within the window
    held_s: float                        # chip-seconds held
    held_busy_s: float                   # busy chip-seconds while held
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.held_busy_s / self.held_s


def reduce(tr: Trace, held: Sequence[Tuple[float, float, Sequence[int]]],
           top: int = 10) -> Reduced:
    """``held``: (start, end, chips) on the trace's clock, covering the
    window; a chip counts toward the idle share only while the job holds it.
    Gaps are named by the host span open at their midpoint, on the first
    chip of the first held interval (which the job holds throughout)."""
    lo, hi = held[0][0], held[-1][1]
    busy = {c: merge([(a, b) for a, b, _ in ops]) for c, ops in tr.ops.items()}
    held_s = held_busy = 0.0
    for a, b, chips in held:
        for c in chips:
            held_s += b - a
            held_busy += covered(busy.get(c, []), a, b)
    per_op: Dict[str, float] = defaultdict(float)
    for ops in tr.ops.values():
        for a, b, name in ops:
            if a >= lo and b <= hi:
                per_op[name] += (b - a) / len(tr.ops)
    # of spans that open together, the inner (shorter) one sorts later
    spans = sorted(tr.spans, key=lambda s: (s[0], -s[1]))
    starts = [s[0] for s in spans]
    by_span: Dict[str, float] = defaultdict(float)
    for a, b in gaps(busy.get(held[0][2][0], []), lo, hi):
        by_span[span_at(spans, starts, (a + b) / 2)] += b - a
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(window_s=hi - lo,
                   busy_s={c: covered(m, lo, hi) for c, m in busy.items()},
                   held_s=held_s, held_busy_s=held_busy,
                   top_ops=rank(per_op), idle_gaps=rank(by_span))
