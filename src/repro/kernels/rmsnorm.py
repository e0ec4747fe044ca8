"""Fused RMSNorm row kernel (Pallas TPU)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import on_platform

# bytes of one (block_rows, D) tile at f32: the input and output tiles are
# double-buffered and the body holds two f32 temporaries of the same size,
# so ~6 tiles must fit the 16 MiB of VMEM a kernel may use by default
TILE_BYTES = 1 << 20


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                    # (rows, D)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * w_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _block_rows(D: int) -> int:
    """Rows per tile: as many as keep a tile within ``TILE_BYTES``, a
    multiple of 8 sublanes, at least 8."""
    return max(8, TILE_BYTES // (4 * D) // 8 * 8)


def rmsnorm_pallas(x, w, *, eps: float = 1e-5,
                   interpret: Optional[bool] = None):
    """x: (..., D); w: (D,)."""
    return on_platform(functools.partial(_rmsnorm_call, eps=eps), x, w,
                       interpret=interpret)


def _rmsnorm_call(x, w, *, eps: float, interpret: bool):
    orig_shape = x.shape
    D = orig_shape[-1]
    xf = x.reshape(-1, D)
    R = xf.shape[0]
    block_rows = min(_block_rows(D), R)
    pad = (-R) % block_rows
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    n = xf.shape[0] // block_rows
    kernel = functools.partial(_rmsnorm_kernel, eps=eps)
    out = pl.pallas_call(
        kernel,
        name="rmsnorm",
        grid=(n,),
        in_specs=[pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
                  pl.BlockSpec((D,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
    )(xf, w)
    if pad:
        out = out[:R]
    return out.reshape(orig_shape)
