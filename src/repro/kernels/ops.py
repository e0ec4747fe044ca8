"""Jitted wrappers around the Pallas kernels, with a global enable switch.

Each kernel compiles natively where it is lowered for a TPU and runs in
interpret mode elsewhere (``kernels/platform.py``).  Model code consults
``pallas_enabled()``, which is off unless ``REPRO_USE_PALLAS=1`` or
``set_pallas(True)``: off, train and prefill take the XLA path
(``kernels/blocked.py``, the jnp SSD scan).

The flash-attention wrapper attaches a custom VJP whose backward pass
recomputes attention via the memory-efficient reference path (flash-style
recompute — nothing quadratic is saved between fwd and bwd).
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_chunked_pallas

_STATE = {"enabled": os.environ.get("REPRO_USE_PALLAS", "0") == "1"}


def pallas_enabled() -> bool:
    return _STATE["enabled"]


def set_pallas(enabled: bool):
    _STATE["enabled"] = enabled


# ---------------------------------------------------------------------------
# flash attention (fwd kernel + recompute bwd)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, scale, interpret):
    return flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                               interpret=interpret)


def _flash_fwd(q, k, v, causal, scale, interpret):
    out = flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                              interpret=interpret)
    return out, (q, k, v)


def _flash_bwd(causal, scale, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.flash_attention_ref(
            q_, k_, v_, causal=causal, scale=scale), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, scale: float = None,
                    interpret: Optional[bool] = None):
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    return _flash(q, k, v, causal, scale, interpret)


# ---------------------------------------------------------------------------
# SSD chunked scan (fwd kernel + recompute bwd via the jnp chunked path)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, a_log, b, c, chunk, interpret):
    return ssd_chunked_pallas(x, dt, a_log, b, c, chunk=chunk,
                              interpret=interpret)


def _ssd_fwd(x, dt, a_log, b, c, chunk, interpret):
    out = ssd_chunked_pallas(x, dt, a_log, b, c, chunk=chunk,
                             interpret=interpret)
    return out, (x, dt, a_log, b, c)


def _ssd_bwd(chunk, interpret, res, g):
    x, dt, a_log, b, c = res
    from repro.models.ssm import ssd_chunked
    _, vjp = jax.vjp(
        lambda x_, dt_, a_, b_, c_: ssd_chunked(x_, dt_, a_, b_, c_,
                                                chunk=chunk),
        x, dt, a_log, b, c)
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, a_log, b, c, *, chunk: int = 128,
        interpret: Optional[bool] = None):
    return _ssd(x, dt, a_log, b, c, chunk, interpret)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def rmsnorm(x, w, *, eps: float = 1e-5, interpret: Optional[bool] = None):
    return rmsnorm_pallas(x, w, eps=eps, interpret=interpret)
