"""Native or interpreted Pallas, chosen from where a call is lowered.

A Pallas TPU kernel only compiles for a TPU; everywhere else it runs in
interpret mode.  The choice follows the platform the call is lowered for,
which is the platform of the arrays passed (or of the enclosing ``jit``),
never the process's default backend: a run whose arrays never reached the
chip cannot pass for one that did.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax


def on_platform(kernel: Callable, *args, interpret: Optional[bool] = None):
    """``kernel(*args, interpret=...)`` compiled for a TPU, interpreted
    elsewhere.  ``interpret`` forces one mode (tests pass ``True``)."""
    if interpret is not None:
        return kernel(*args, interpret=interpret)
    return jax.lax.platform_dependent(
        *args, tpu=functools.partial(kernel, interpret=False),
        default=functools.partial(kernel, interpret=True))
