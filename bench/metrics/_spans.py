"""What the readers of the program's own spans share.

The spans are those of ``repro.obs.live``; a program without that module
has none, and each reader then gives ``None``.
"""
from __future__ import annotations

import statistics
from typing import Dict, Iterable, List, Optional


def live():
    """``repro.obs.live``, or ``None`` where the program has no such module."""
    try:
        from repro.obs import live as mod
    except ImportError:
        return None
    return mod


def window_steps(rec) -> List[int]:
    """The step ids of the window: the last ``len(rec.step_s)`` of
    ``trainer.step``.  After the window only the plain reference runs, and
    it never calls ``ElasticTrainer.step()``."""
    mod = live()
    if mod is None or not rec.step_s:
        return []
    ids = [s.step for s in mod.spans("trainer.step")]
    return ids[-len(rec.step_s):]


def median_per_step(rec, names: Iterable[str]) -> Optional[float]:
    """The median over the window's steps of the summed seconds of the
    spans ``names`` of each step."""
    steps = window_steps(rec)
    if not steps:
        return None
    mod, want = live(), set(steps)
    per: Dict[int, float] = dict.fromkeys(steps, 0.0)
    seen = set()
    for name in names:
        for s in mod.spans(name):
            if s.step in want:
                per[s.step] += s.seconds
                seen.add(s.step)
    if seen != want:
        return None
    return statistics.median(per.values())


def total(name: str) -> Optional[float]:
    """The summed seconds of every span ``name`` of the run."""
    mod = live()
    got = mod.spans(name) if mod else []
    return sum(s.seconds for s in got) if got else None
