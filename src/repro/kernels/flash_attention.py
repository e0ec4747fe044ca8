"""Causal GQA flash attention — Pallas TPU kernel.

TPU-native design (not a CUDA port): the grid is (batch, q_head, q_block,
k_block) with the k dimension innermost and *revisiting* the same output
block, so the online-softmax accumulators live in VMEM scratch across k steps.
Tiles are MXU-aligned (block_q x head_dim and block_k x head_dim, both 128 by
default).  Causal q-blocks skip k-blocks entirely above the diagonal.

GQA is handled in the k/v index maps (q head h reads kv head h // group_size),
so repeated KV is never materialized in HBM or VMEM.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, block_q: int, block_k: int, nk: int,
                  causal: bool):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    # causal: the whole k-block is masked iff k_start > q_end
    run = (k_start <= q_start + block_q - 1) if causal else (ik >= 0)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq,bk)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]                                   # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                                # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                       # (bq, 1)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, scale: float = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: Optional[bool] = None):
    """q: (B,S,H,hd); k,v: (B,S,KV,hd) -> (B,S,H,hd)."""
    return on_platform(
        functools.partial(_flash_fwd, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k),
        q, k, v, interpret=interpret)


def _flash_fwd(q, k, v, *, causal: bool, scale: Optional[float],
               block_q: int, block_k: int, interpret: bool):
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq, nk = S // block_q, S // block_k

    qt = q.transpose(0, 2, 1, 3)         # (B,H,S,hd)
    kt = k.transpose(0, 2, 1, 3)         # (B,KV,S,hd)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_flash_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, nk=nk, causal=causal)
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
