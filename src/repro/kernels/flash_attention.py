"""Causal GQA flash attention — Pallas TPU forward and backward kernels.

Layout: the kernels read the model's (B, S, H, hd) q, o, dO, dq feature-major,
as (B, H, hd, S), and k, v, dk, dv as (B, KV, hd, S).  That is the physical
layout XLA gives the attention projections' outputs on a TPU (sequence
minor), so the transposes around the kernels are bitcasts, not copies.  A
head's block is a (hd, rows) tile: queries and keys lie on lanes, and every
softmax statistic is a (1, block_q) row, so no kernel reduces across lanes.
hd lies on sublanes, so any multiple of 64 tiles whole (8, 128) f32 and
(16, 128) bf16 tiles (``fits``); below 128 the products that contract over
hd fill half the MXU's depth and each grid step does less work, so the
blocks are chosen by head size (``blocks``).

One grid step serves the G = H/KV query heads of one KV head: the q block
is heads [j*G, (j+1)*G) and the k/v block KV head j.  GQA is thus in the
index maps; repeated KV is never materialised, and each K/V block is
fetched once for G heads.

- ``flash_attention_fwd``, grid (B, KV, nq, nk), k innermost and revisiting
  the output block: s^T = k q^T, an online softmax over sublanes with m, l
  and o^T = v^T p^T in VMEM scratch.  Returns o and the f32 row
  log-sum-exp ``lse``, (B, H, 1, S).
- ``flash_attention_dkv``, grid (B, KV, nk, nq), FlashAttention-2's
  backward: p^T = exp(s^T - lse), ds^T = p^T (v dO^T - delta),
  dv^T += dO^T p, dk^T += q^T ds.
- ``flash_attention_dq``, grid (B, KV, nq, nk): the same p^T and ds^T,
  dq^T += k^T ds^T.

Both backward kernels recompute p from ``lse`` in VMEM and take
``delta = rowsum(dO * o)`` in f32.  Causal: a block wholly above the
diagonal is neither computed nor fetched (its index map is clamped to a
block the step next to it holds, which Pallas does not fetch again); only
blocks that cross the diagonal are masked.  Matmuls run at the default
precision; softmax statistics and accumulators are f32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import on_platform

NEG_INF = float(jnp.finfo(jnp.float32).min)
# preferred (block_q, block_k) of each kernel by head size, from sweeps on
# one v5e chip in f32 (recorded in PERF.md): hd 128 at (4, 2048, 32/4, 128),
# hd 64 at (2, 4096, 32/8, 64)
_BLOCKS = {
    128: {"fwd": (512, 512), "dkv": (512, 512), "dq": (512, 512)},
    64: {"fwd": (512, 1024), "dkv": (512, 512), "dq": (512, 512)},
}
_HEAD = 64              # heads are a whole number of these rows
_LANES = 128
_VMEM_LIMIT = 64 * 2**20
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def blocks(hd: int, kernel: str) -> tuple:
    """Preferred (block_q, block_k) of ``kernel`` ("fwd", "dkv" or "dq")
    at head size ``hd``: hd 64's sweep below 128, hd 128's from there."""
    return _BLOCKS[64 if hd < 128 else 128][kernel]


def _block(S: int, preferred: int, given: Optional[int]) -> int:
    """``given``, else the largest of preferred, preferred/2, ... that
    divides S (S itself where S is shorter)."""
    if given is not None:
        return min(given, S)
    b = min(preferred, S)
    while S % b:
        b //= 2
    return b


def _checked_blocks(kernel: str, S: int, hd: int, block_q: Optional[int],
                    block_k: Optional[int]) -> tuple:
    """``kernel``'s (block_q, block_k) at (S, hd): the given sizes, else
    the preferred ones that divide S."""
    pq, pk = blocks(hd, kernel)
    bq, bk = _block(S, pq, block_q), _block(S, pk, block_k)
    assert fits(S, hd) and S % bq == 0 and S % bk == 0, (S, hd, bq, bk)
    return bq, bk


def fits(S: int, hd: int) -> bool:
    """Whether the kernels take sequence length ``S`` and head size ``hd``:
    heads of whole 64-row tiles (hd on sublanes) and lane-aligned rows
    (every block size then divides S)."""
    return hd % _HEAD == 0 and S % _LANES == 0


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _tile(ref, g):
    """Head ``g``'s (hd, rows) tile of a block, as f32."""
    return ref[0, g].astype(jnp.float32)


def _by_causal_block(body, q_start, bq, k_start, bk):
    """Run ``body(masked)`` for a (q block, k block) pair: unmasked below
    the diagonal, masked across it, not at all above it."""
    below = k_start + bk - 1 <= q_start
    crosses = jnp.logical_and(k_start <= q_start + bq - 1,
                              jnp.logical_not(below))
    pl.when(below)(lambda: body(False))
    pl.when(crosses)(lambda: body(True))


def _keep(q_start, k_start, bk, bq):
    """Causal mask of a (keys, queries) tile."""
    kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    return qp >= kp


def _k_block(bq, bk):
    """k block ``t`` of q block ``i``, held at the last one it needs."""
    return lambda i, t: jnp.minimum(t, (i * bq + bq - 1) // bk)


def _q_block(bq, bk):
    """q block ``t`` of k block ``i``, held at the first one it needs."""
    return lambda i, t: jnp.maximum(t, (i * bk) // bq)


def _feature_major(x):
    """(B, S, N, hd) -> (B, N, hd, S)."""
    return x.transpose(0, 2, 3, 1)


def _from_feature_major(x):
    return x.transpose(0, 3, 1, 2)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, G, bq, bk, nk):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start, k_start = iq * bq, ik * bk

    def body(masked):
        k = _tile(k_ref, 0).T                                 # (bk, hd)
        vt = _tile(v_ref, 0)                                  # (hd, bk)
        keep = _keep(q_start, k_start, bk, bq) if masked else None
        for g in range(G):
            st = _dot(k, _tile(q_ref, g) * scale, _NN)        # (bk, bq)
            if masked:
                st = jnp.where(keep, st, NEG_INF)
            m_prev = m_ref[g]                                 # (1, bq)
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            pt = jnp.exp(st - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[g] = alpha * l_ref[g] + jnp.sum(pt, axis=0, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + _dot(vt, pt, _NN)  # (hd, bq)
            m_ref[g] = m_new

    _by_causal_block(body, q_start, bq, k_start, bk)

    @pl.when(ik == nk - 1)
    def _finish():
        for g in range(G):
            l = l_ref[g]
            o_ref[0, g] = (acc_ref[g] / l).astype(o_ref.dtype)
            lse_ref[0, g] = m_ref[g] + jnp.log(l)


def _fwd(q, k, v, *, scale, block_q, block_k, interpret):
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    bq, bk = _checked_blocks("fwd", S, hd, block_q, block_k)
    nq, nk = S // bq, S // bk
    kernel = functools.partial(_fwd_kernel, scale=scale, G=G, bq=bq, bk=bk,
                               nk=nk)
    kb = _k_block(bq, bk)
    q_spec = pl.BlockSpec((1, G, hd, bq), lambda b, j, i, t: (b, j, 0, i))
    kv_spec = pl.BlockSpec((1, 1, hd, bk),
                           lambda b, j, i, t: (b, j, 0, kb(i, t)))
    o, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(B, KV, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec,
                   pl.BlockSpec((1, G, 1, bq),
                                lambda b, j, i, t: (b, j, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, H, hd, S), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((G, hd, bq), jnp.float32),
                        pltpu.VMEM((G, 1, bq), jnp.float32),
                        pltpu.VMEM((G, 1, bq), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(_feature_major(q), _feature_major(k), _feature_major(v))
    return _from_feature_major(o), lse


def flash_attention_fwd(q, k, v, *, scale: float = None,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """q: (B,S,H,hd); k,v: (B,S,KV,hd) -> (o (B,S,H,hd), lse (B,H,1,S) f32)."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    return on_platform(
        functools.partial(_fwd, scale=scale, block_q=block_q,
                          block_k=block_k),
        q, k, v, interpret=interpret)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _p_ds(k, v, qt, dot_, lse, delta, keep):
    """p^T and ds^T of one head, (keys, queries) tiles, from k, v (bk, hd)
    and the head's q^T (scaled) and dO^T (hd, bq)."""
    st = _dot(k, qt, _NN)
    if keep is not None:
        st = jnp.where(keep, st, NEG_INF)
    pt = jnp.exp(st - lse)
    return pt, pt * (_dot(v, dot_, _NN) - delta)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, scale, G, bq, bk, nq):
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start, k_start = iq * bq, ik * bk

    def body(masked):
        k, v = _tile(k_ref, 0).T, _tile(v_ref, 0).T           # (bk, hd)
        keep = _keep(q_start, k_start, bk, bq) if masked else None
        dkt, dvt = dk_acc[...], dv_acc[...]                   # (hd, bk)
        for g in range(G):
            qt, dot_ = _tile(q_ref, g) * scale, _tile(do_ref, g)
            pt, dst = _p_ds(k, v, qt, dot_, lse_ref[0, g], delta_ref[0, g],
                            keep)
            dvt = dvt + _dot(dot_, pt, _NT)
            dkt = dkt + _dot(qt, dst, _NT)
        dk_acc[...] = dkt
        dv_acc[...] = dvt

    # the q blocks wholly above this k block come first in t; they hold
    # the first needed block (the clamp) and skip
    _by_causal_block(body, q_start, bq, k_start, bk)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale, G, bq, bk, nk):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start, k_start = iq * bq, ik * bk

    def body(masked):
        kt = _tile(k_ref, 0)                                  # (hd, bk)
        k, v = kt.T, _tile(v_ref, 0).T                        # (bk, hd)
        keep = _keep(q_start, k_start, bk, bq) if masked else None
        for g in range(G):
            _, dst = _p_ds(k, v, _tile(q_ref, g) * scale, _tile(do_ref, g),
                           lse_ref[0, g], delta_ref[0, g], keep)
            dq_acc[g] = dq_acc[g] + _dot(kt, dst, _NN)        # (hd, bq)

    _by_causal_block(body, q_start, bq, k_start, bk)

    @pl.when(ik == nk - 1)
    def _finish():
        for g in range(G):
            dq_ref[0, g] = (dq_acc[g] * scale).astype(dq_ref.dtype)


def _dkv(qt, kt, vt, dot_, lse, delta, *, scale, bq, bk, interpret):
    """dk, dv feature-major: k block fixed on grid axis 2, q blocks
    innermost."""
    B, H, hd, S = qt.shape
    KV = kt.shape[1]
    G = H // KV
    qb = _q_block(bq, bk)
    q_in = pl.BlockSpec((1, G, hd, bq),
                        lambda b, j, i, t: (b, j, 0, qb(i, t)))
    row_in = pl.BlockSpec((1, G, 1, bq),
                          lambda b, j, i, t: (b, j, 0, qb(i, t)))
    kv_fixed = pl.BlockSpec((1, 1, hd, bk), lambda b, j, i, t: (b, j, 0, i))
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, G=G, bq=bq, bk=bk,
                          nq=S // bq),
        name="flash_attention_dkv",
        grid=(B, KV, S // bk, S // bq),
        in_specs=[q_in, kv_fixed, kv_fixed, q_in, row_in, row_in],
        out_specs=[kv_fixed, kv_fixed],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
        scratch_shapes=[pltpu.VMEM((hd, bk), jnp.float32),
                        pltpu.VMEM((hd, bk), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(qt, kt, vt, dot_, lse, delta)


def _dq(qt, kt, vt, dot_, lse, delta, *, scale, bq, bk, interpret):
    """dq feature-major: q block fixed on grid axis 2, k blocks
    innermost."""
    B, H, hd, S = qt.shape
    KV = kt.shape[1]
    G = H // KV
    kb = _k_block(bq, bk)
    q_fixed = pl.BlockSpec((1, G, hd, bq), lambda b, j, i, t: (b, j, 0, i))
    row_fixed = pl.BlockSpec((1, G, 1, bq), lambda b, j, i, t: (b, j, 0, i))
    kv_in = pl.BlockSpec((1, 1, hd, bk),
                         lambda b, j, i, t: (b, j, 0, kb(i, t)))
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, G=G, bq=bq, bk=bk,
                          nk=S // bk),
        name="flash_attention_dq",
        grid=(B, KV, S // bq, S // bk),
        in_specs=[q_fixed, kv_in, kv_in, q_fixed, row_fixed, row_fixed],
        out_specs=q_fixed,
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        scratch_shapes=[pltpu.VMEM((G, hd, bq), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(qt, kt, vt, dot_, lse, delta)


def _bwd(q, k, v, o, lse, do, *, scale, block_q, block_k, interpret):
    S, hd = q.shape[1], q.shape[-1]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta.transpose(0, 2, 1)[:, :, None, :]           # (B,H,1,S)
    args = [_feature_major(x) for x in (q, k, v, do)] + [lse, delta]
    bq, bk = _checked_blocks("dkv", S, hd, block_q, block_k)
    dk, dv = _dkv(*args, scale=scale, bq=bq, bk=bk, interpret=interpret)
    bq, bk = _checked_blocks("dq", S, hd, block_q, block_k)
    dq = _dq(*args, scale=scale, bq=bq, bk=bk, interpret=interpret)
    return (_from_feature_major(dq), _from_feature_major(dk),
            _from_feature_major(dv))


def flash_attention_bwd(q, k, v, o, lse, do, *, scale: float = None,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: Optional[bool] = None):
    """Gradients (dq, dk, dv) of ``flash_attention_fwd``'s o, given its
    residuals o and lse and the cotangent do."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    return on_platform(
        functools.partial(_bwd, scale=scale, block_q=block_q,
                          block_k=block_k),
        q, k, v, o, lse, do, interpret=interpret)
