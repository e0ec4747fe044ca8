"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

TPU adaptation of the SSD algorithm (arXiv 2405.21060 §6): the sequence is
processed in chunks of Q tokens.  Within a chunk the dual "attention" form is
three MXU matmuls ((Q,N)x(N,Q), (Q,Q)x(Q,P), (Q,N)x(N,P)); across chunks the
(P,N) state is carried in VMEM scratch through the sequentially-iterated chunk
grid dimension.  Cumulative decays are masked sums over a (Q, Q) triangle.

Layout is head-major, like the flash kernel: every per-head operand is
transposed to ``(B, H, L, ·)`` so each block's last two dims are a chunk of
the sequence and a full feature dim, which the TPU's (8, 128) tiling accepts.
``dt`` comes in twice, as a column ``(Q, 1)`` and as a row ``(1, Q)``, so the
kernel never transposes a vector; ``A = -exp(a_log)`` sits whole in SMEM and
is read per head.

Grid: (batch, head, chunk) with chunk innermost ("arbitrary" = sequential).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import on_platform


def _ssd_kernel(a_ref, x_ref, dtc_ref, dtr_ref, b_ref, c_ref, y_ref, h_ref):
    h_idx = pl.program_id(1)
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)                  # (Q, P)
    dt_col = dtc_ref[0, 0].astype(jnp.float32)           # (Q, 1)
    dt_row = dtr_ref[0, 0].astype(jnp.float32)           # (1, Q)
    b = b_ref[0, 0].astype(jnp.float32)                  # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)                  # (Q, N)
    A = a_ref[h_idx]                                     # scalar (SMEM)

    dA_col = dt_col * A                                  # (Q, 1)
    dA_row = dt_row * A                                  # (1, Q)
    Q = x.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = rows >= cols
    # inclusive cumulative sums of dA, as a column and as a row
    cum_col = jnp.sum(jnp.where(causal, dA_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(rows <= cols, dA_col, 0.0), axis=0,
                      keepdims=True)

    # --- intra-chunk quadratic term ---
    # mask before exp: above the diagonal the exponent is positive
    decay = jnp.exp(jnp.where(causal, cum_col - cum_row, -jnp.inf))  # (Q,Q)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q,Q)
    scores = cb * decay * dt_row                                  # dt_s on cols
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # (Q,P)

    # --- inter-chunk contribution: C_t . h_prev, scaled by exp(cum_t) ---
    h_prev = h_ref[...]                                            # (P,N)
    y_inter = jax.lax.dot_general(c, h_prev, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (Q,P)
    y = y + jnp.exp(cum_col) * y_inter
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # --- state update: h = exp(cum_Q) h_prev + X^T (tail*dt*B) ---
    total = jnp.sum(dA_row, axis=1, keepdims=True)                 # (1,1)
    tail = jnp.exp(total - cum_col)                                # (Q,1)
    hb = jax.lax.dot_general(x, b * (tail * dt_col), (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (P,N)
    h_ref[...] = jnp.exp(total) * h_prev + hb


def ssd_chunked_pallas(x, dt, a_log, b, c, *, chunk: int = 128,
                       interpret: Optional[bool] = None):
    """x: (B,L,H,P); dt: (B,L,H); a_log: (H,); b,c: (B,L,G,N) -> (B,L,H,P)."""
    return on_platform(functools.partial(_ssd_call, chunk=chunk),
                       x, dt, a_log, b, c, interpret=interpret)


def _ssd_call(x, dt, a_log, b, c, *, chunk: int, interpret: bool):
    B, L, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    nc = L // Q

    a = -jnp.exp(a_log.astype(jnp.float32))                        # (H,)
    xt = x.transpose(0, 2, 1, 3)                                   # (B,H,L,P)
    dtt = dt.transpose(0, 2, 1)                                    # (B,H,L)
    bt = b.transpose(0, 2, 1, 3)                                   # (B,G,L,N)
    ct = c.transpose(0, 2, 1, 3)
    y = pl.pallas_call(
        _ssd_kernel,
        name="ssd_scan",
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, Q, P), lambda bi, h, n: (bi, h, n, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda bi, h, n: (bi, h, n, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda bi, h, n: (bi, h, 0, n)),
            pl.BlockSpec((1, 1, Q, N), lambda bi, h, n: (bi, h // rep, n, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda bi, h, n: (bi, h // rep, n, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda bi, h, n: (bi, h, n, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, L, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, xt, dtt[..., None], dtt[:, :, None, :], bt, ct)
    return y.transpose(0, 2, 1, 3)
