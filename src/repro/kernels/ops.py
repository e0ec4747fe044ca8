"""Jitted wrappers around the Pallas kernels.

Each kernel compiles natively where it is lowered for a TPU and runs in
interpret mode elsewhere (``kernels/platform.py``).  How each is reached:

- ``causal_attention``: ``models/attention.py`` sends it the shapes the
  kernels take; it is the Pallas flash op (forward and backward kernels,
  ``kernels/flash_attention.py``) where the call is lowered for a TPU and
  ``kernels/blocked.py``'s XLA scan elsewhere.
- ``ssd``: the Pallas SSD forward kernel (``kernels/ssd_scan.py``), whose
  backward re-runs the jnp chunked scan.  Only tests call it; the model runs
  ``models/ssm.ssd_chunked`` on every platform.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.blocked import blocked_attention
from repro.kernels.flash_attention import (flash_attention_bwd,
                                           flash_attention_fwd)
from repro.kernels.ssd_scan import ssd_chunked_pallas


# ---------------------------------------------------------------------------
# flash attention (Pallas forward and backward kernels)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, block_q, block_k, interpret):
    return flash_attention_fwd(q, k, v, scale=scale, block_q=block_q,
                               block_k=block_k, interpret=interpret)[0]


def _flash_fwd(q, k, v, scale, block_q, block_k, interpret):
    o, lse = flash_attention_fwd(q, k, v, scale=scale, block_q=block_q,
                                 block_k=block_k, interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, block_q, block_k, interpret, res, do):
    return flash_attention_bwd(*res, do, scale=scale, block_q=block_q,
                               block_k=block_k, interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, scale: float = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Causal attention, q: (B,S,H,hd); k,v: (B,S,KV,hd) -> (B,S,H,hd),
    differentiable."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    return _flash(q, k, v, scale, block_q, block_k, interpret)


def causal_attention(q, k, v, *, scale: float):
    """Causal self-attention: the Pallas flash op where the call is lowered
    for a TPU, the blocked XLA scan elsewhere.  The caller checks that the
    kernels take the shapes (``flash_attention.fits``)."""
    return jax.lax.platform_dependent(
        q, k, v,
        tpu=functools.partial(flash_attention, scale=scale, interpret=False),
        default=lambda q, k, v: blocked_attention(q, k, v, True, scale))


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
