"""Blocked (flash-style) attention in pure jnp — the XLA-lowerable twin of
``kernels/flash_attention.py``.

Used wherever the Pallas kernels do not run: off a TPU (the CPU tests, the
multi-pod dry-run, which lowers on the CPU backend), on meshes of more than
one device, and for the train and prefill calls the kernels do not take
(non-causal or cross-attention, MLA's unequal q/v head sizes, head sizes
not a multiple of 64 or lengths not of 128; ``models/attention.py``
``_use_flash``).  A
``lax.scan`` over KV blocks with online softmax keeps the live working set
at one (B,KV,G,Sq,block_k) tile instead of the full O(Sq x Sk) score matrix
(2.1 GB/device/tensor on yi-6b train_4k — see EXPERIMENTS.md §Perf
iteration 1).

The backward pass is the standard flash recomputation: only (out, lse) are
saved; dq/dk/dv are accumulated in a second scan over KV blocks.  FLOPs ~2x
attention fwd, memory O(block).  GQA is handled in grouped form throughout —
repeated KV is never materialized.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG = jnp.finfo(jnp.float32).min
DEFAULT_BLOCK_K = 512


def _pad_blocks(x, block: int, axis: int):
    n = x.shape[axis]
    pad = (-n) % block
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x, n + pad


def _fwd_scan(qg, k, v, *, causal: bool, scale: float, q_pos0, kv_len,
              block_k: int):
    """qg: (B,Sq,KV,G,hd); k,v: (B,Skp,KV,hd) already padded to block_k.
    Returns (out (B,Sq,KV,G,hd) f32, lse (B,KV,G,Sq) f32)."""
    B, Sq, KV, G, hd = qg.shape
    hdv = v.shape[-1]
    Skp = k.shape[1]
    nb = Skp // block_k
    kb = k.reshape(B, nb, block_k, KV, hd).swapaxes(0, 1)
    vb = v.reshape(B, nb, block_k, KV, hdv).swapaxes(0, 1)
    qf = qg.astype(jnp.float32) * scale
    spos = q_pos0 + jnp.arange(Sq)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, j = inp
        s = jnp.einsum("bskgh,btkh->bkgst", qf, kblk.astype(jnp.float32))
        tpos = j * block_k + jnp.arange(block_k)
        valid = (tpos < kv_len)[None, None, None, None, :] if kv_len is not None \
            else jnp.ones((1, 1, 1, 1, block_k), bool)
        if causal:
            valid = valid & (spos[:, None] >= tpos[None, :])[None, None, None]
        s = jnp.where(valid, s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkh->bkgsh", p, vblk.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((B, KV, G, Sq), NEG, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, hdv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kb, vb, jnp.arange(nb)))
    l_safe = jnp.maximum(l, 1e-30)
    out = acc / l_safe[..., None]
    lse = m + jnp.log(l_safe)
    out = out.transpose(0, 3, 1, 2, 4)        # (B,Sq,KV,G,hd)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def blocked_attention(q, k, v, causal: bool = True,
                      scale: Optional[float] = None, q_pos0: int = 0,
                      kv_len: Optional[int] = None,
                      block_k: int = DEFAULT_BLOCK_K):
    """q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd) -> (B,Sq,H,hd) in q.dtype.

    kv_len: static or traced upper bound on valid kv positions (decode).
    """
    out, _ = _blocked_fwd_impl(q, k, v, causal, scale, q_pos0, kv_len, block_k)
    return out


def _blocked_fwd_impl(q, k, v, causal, scale, q_pos0, kv_len, block_k):
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    hdv = v.shape[-1]
    scale = hd ** -0.5 if scale is None else scale
    block_k = min(block_k, max(k.shape[1], 1))
    kp, Skp = _pad_blocks(k, block_k, 1)
    vp, _ = _pad_blocks(v, block_k, 1)
    if kv_len is None and Skp != k.shape[1]:
        kv_len = k.shape[1]
    qg = q.reshape(B, Sq, KV, G, hd)
    out, lse = _fwd_scan(qg, kp, vp, causal=causal, scale=scale,
                         q_pos0=q_pos0, kv_len=kv_len, block_k=block_k)
    return out.reshape(B, Sq, H, hdv).astype(q.dtype), lse


def _blocked_vjp_fwd(q, k, v, causal, scale, q_pos0, kv_len, block_k):
    out, lse = _blocked_fwd_impl(q, k, v, causal, scale, q_pos0, kv_len,
                                 block_k)
    return out, (q, k, v, out, lse)


def _blocked_vjp_bwd(causal, scale, q_pos0, kv_len, block_k, res, dout):
    q, k, v, out, lse = res
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    hdv = v.shape[-1]
    scale_v = hd ** -0.5 if scale is None else scale
    block_k = min(block_k, max(k.shape[1], 1))
    Sk = k.shape[1]
    kp, Skp = _pad_blocks(k, block_k, 1)
    vp, _ = _pad_blocks(v, block_k, 1)
    if kv_len is None and Skp != Sk:
        kv_len = Sk
    nb = Skp // block_k
    kb = kp.reshape(B, nb, block_k, KV, hd).swapaxes(0, 1)
    vb = vp.reshape(B, nb, block_k, KV, hdv).swapaxes(0, 1)

    qg = q.reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    og = out.reshape(B, Sq, KV, G, hdv).astype(jnp.float32)
    dog = dout.reshape(B, Sq, KV, G, hdv).astype(jnp.float32)
    # D = rowsum(dout * out): (B,KV,G,Sq)
    delta = jnp.einsum("bskgh,bskgh->bkgs", dog, og)
    spos = q_pos0 + jnp.arange(Sq)

    def body(dq_acc, inp):
        kblk, vblk, j = inp
        kf, vf = kblk.astype(jnp.float32), vblk.astype(jnp.float32)
        s = jnp.einsum("bskgh,btkh->bkgst", qg, kf) * scale_v
        tpos = j * block_k + jnp.arange(block_k)
        valid = (tpos < kv_len)[None, None, None, None, :] if kv_len is not None \
            else jnp.ones((1, 1, 1, 1, block_k), bool)
        if causal:
            valid = valid & (spos[:, None] >= tpos[None, :])[None, None, None]
        p = jnp.where(valid, jnp.exp(s - lse[..., None]), 0.0)
        dv_blk = jnp.einsum("bkgst,bskgh->btkh", p, dog)
        dp = jnp.einsum("bskgh,btkh->bkgst", dog, vf)
        ds = p * (dp - delta[..., None]) * scale_v
        dq_acc = dq_acc + jnp.einsum("bkgst,btkh->bskgh", ds, kf)
        dk_blk = jnp.einsum("bkgst,bskgh->btkh", ds, qg)
        return dq_acc, (dk_blk, dv_blk)

    dq0 = jnp.zeros((B, Sq, KV, G, hd), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(body, dq0, (kb, vb, jnp.arange(nb)))
    dk = dks.swapaxes(0, 1).reshape(B, Skp, KV, hd)[:, :Sk]
    dv = dvs.swapaxes(0, 1).reshape(B, Skp, KV, hdv)[:, :Sk]
    return (dq.reshape(B, Sq, H, hd).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype))


blocked_attention.defvjp(_blocked_vjp_fwd, _blocked_vjp_bwd)
