"""The numbers that decide ``correct``, each held against its limit.

- ``loss_gap``: the largest relative gap between a checked step's loss and
  the reference's.
- ``grad_gap``: over the leaves, the largest gap between the norm of the
  program's first clipped gradient and the reference's, measured against the
  reference's norm of that leaf or of the median leaf, whichever is larger.
- ``change_gap``: the same for the norm of each leaf's change after the
  checked steps, over the leaves whose reference gradient is at least a
  thousandth of the median leaf's (a gradient that is nought to rounding
  moves its leaf by round-off alone under Adam).
- ``state_mismatch``: leaves whose bits differ across an elastic event (an
  exact comparison, limit 0).
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, Optional, Tuple

IGNORE_BELOW = 1e-3


def worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
               leaves=None) -> Tuple[float, str]:
    leaves = sorted(ref if leaves is None else leaves)
    med = statistics.median(ref[k] for k in ref)
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in leaves}
    # a NaN reading wins, so that a non-finite leaf is never passed over
    k = max(gaps, key=lambda k: (math.isnan(gaps[k]), gaps[k]))
    return gaps[k], k


def numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` and ``ref``: losses, first_grad and change as the reference
    returns them."""
    loss = max((abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                     ref["losses"])),
               key=lambda x: (math.isnan(x), x))
    grad, _ = worst_leaf(prog["first_grad"], ref["first_grad"])
    med = statistics.median(ref["first_grad"].values())
    moved = [k for k, g in ref["first_grad"].items()
             if g >= IGNORE_BELOW * med]
    change, _ = worst_leaf(prog["change"], ref["change"], moved)
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change}


def verdict(nums: Dict[str, float], limits: Dict[str, Optional[float]]
            ) -> Tuple[bool, Dict[str, dict]]:
    """Each compared number beside its limit; a limit of None is not
    compared (the number is still shown)."""
    checks, ok = {}, True
    for name, value in nums.items():
        limit = limits.get(name)
        checks[name] = {"value": value, "limit": limit}
        if limit is not None and not value <= limit:
            ok = False
    return ok, checks
