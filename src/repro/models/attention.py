"""Attention mixers: GQA softmax attention and DeepSeek-V2 MLA.

All entry points are pure functions of (config, params, activations, cache).
KV caches are plain pytrees so they checkpoint/reshard like parameters
(the elastic runtime treats them identically).

Decode assumes a uniform position across the batch (scalar ``pos``), matching
the serving driver's synchronous batched decode loop.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, rmsnorm
from repro.sharding import can_shard, current_rules, shard_constraint


def _use_flash(mode: str, q, k, v, *, causal: bool, kv_override) -> bool:
    """Whether causal self-attention may take the Pallas flash op (on a TPU;
    ``kernels.ops.causal_attention`` picks by platform): train or prefill,
    q and k of one length from position 0, equal q/k and v head sizes the
    kernels' blocks tile, and one device (under GSPMD a Mosaic call would be
    replicated)."""
    from repro.kernels import flash_attention as fa
    S, hd = q.shape[1], q.shape[-1]
    rules = current_rules()
    one_device = rules is None or rules.mesh is None or rules.mesh.size == 1
    return (causal and kv_override is None and mode in ("train", "prefill")
            and k.shape[1] == S and v.shape[-1] == hd and fa.fits(S, hd)
            and one_device)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    shape = (batch, max_len, kv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def abstract_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    s = jax.ShapeDtypeStruct((batch, max_len, kv, hd), dtype)
    return {"k": s, "v": s}


def _grouped_attention(q, k, v, *, causal: bool, q_pos0, scale: float,
                       kv_len: Optional[jax.Array] = None):
    """q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd). GQA without materializing repeated KV.

    q_pos0: absolute position of q[0] (for causal masking against the cache).
    kv_len: if set, keys at index >= kv_len are masked (decode: cache tail).
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32) * scale
    Sk = k.shape[1]
    tpos = jnp.arange(Sk)
    neg = jnp.finfo(jnp.float32).min
    if causal:
        spos = q_pos0 + jnp.arange(Sq)
        mask = spos[:, None] >= tpos[None, :]
        scores = jnp.where(mask[None, None, None], scores, neg)
    if kv_len is not None:
        scores = jnp.where((tpos < kv_len)[None, None, None, None], scores, neg)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, Sq, H, hd)


def attn_forward(cfg: ModelConfig, p: dict, x, *, positions, mode: str,
                 cache: Optional[dict] = None, pos=None,
                 kv_override=None, causal: bool = True):
    """Returns (out, new_cache).

    kv_override: (k, v) already projected — used for cross-attention where the
    encoder-side KV is computed once at prefill.
    """
    B, S, D = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    # head-parallel attention only when KV heads divide the model axis;
    # otherwise leave activations on the residual (sequence-parallel) layout
    # and let GSPMD propagate (blocked attention regroups H -> (KV, G), so a
    # head-sharding that KV cannot carry would replicate the score tiles).
    head_par = can_shard(KV, "kv_heads") and mode != "decode"
    rope = cfg.position_embedding_type == "rope"
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if head_par:
        q = shard_constraint(q, "batch", None, "heads", None)

    if kv_override is not None:
        k, v = kv_override
        new_cache = cache
        if causal and rope:
            q = apply_rope(q, positions, cfg.rope_theta)
        kv_len = None
    else:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        if head_par:
            k = shard_constraint(k, "batch", None, "kv_heads", None)
            v = shard_constraint(v, "batch", None, "kv_heads", None)
        if cfg.qk_norm:
            q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
        if rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        if mode == "decode":
            assert cache is not None and pos is not None
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos, axis=1)
            new_cache = {"k": ck, "v": cv}
            k, v = ck, cv
            kv_len = pos + S
        else:
            if mode == "prefill":
                new_cache = {"k": k, "v": v}   # caller pads/places into cache
            else:
                new_cache = None
            kv_len = None

    scale = (hd ** -0.5 if cfg.attention_multiplier is None
             else cfg.attention_multiplier)
    if _use_flash(mode, q, k, v, causal=causal, kv_override=kv_override):
        from repro.kernels import ops as kops
        out = kops.causal_attention(q, k, v, scale=scale)
    elif mode == "decode":
        out = _grouped_attention(q, k, v, causal=causal, q_pos0=pos,
                                 scale=scale, kv_len=kv_len)
    else:
        # blocked flash-style path: O(block) memory instead of O(S^2)
        from repro.kernels.blocked import blocked_attention
        out = blocked_attention(q, k, v, causal, scale)
    if head_par:
        out = shard_constraint(out, "batch", None, "heads", None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    a = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_len, a.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_len, a.qk_rope_head_dim), dtype),
    }


def abstract_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    a = cfg.mla
    return {
        "ckv": jax.ShapeDtypeStruct((batch, max_len, a.kv_lora_rank), dtype),
        "krope": jax.ShapeDtypeStruct((batch, max_len, a.qk_rope_head_dim), dtype),
    }


def mla_forward(cfg: ModelConfig, p: dict, x, *, positions, mode: str,
                cache: Optional[dict] = None, pos=None,
                absorb: bool = False):
    """Multi-head latent attention. The cache stores only the compressed
    per-token latent (kv_lora_rank + rope_dim floats) — MLA's memory win.

    absorb=True uses the W_UK-absorption decode path (beyond-paper §Perf
    optimization): scores are computed directly against the latent cache
    without expanding per-head keys/values.
    """
    a = cfg.mla
    B, S, D = x.shape
    H = cfg.num_heads
    nope, rope_d, vd = a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim

    # --- queries ---
    if a.q_lora_rank:
        cq = rmsnorm(jnp.einsum("bsd,dl->bsl", x, p["wq_a"]), p["q_norm"],
                     cfg.norm_eps)
        q = jnp.einsum("bsl,lhk->bshk", cq, p["wq_b"])
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q = shard_constraint(q, "batch", None, "heads", None)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    # --- latent kv ---
    ckv_kr = jnp.einsum("bsd,dl->bsl", x, p["wkv_a"])
    ckv, krope = ckv_kr[..., :a.kv_lora_rank], ckv_kr[..., a.kv_lora_rank:]
    ckv = rmsnorm(ckv, p["kv_norm"], cfg.norm_eps)
    # shared (single-head) rope key
    krope = apply_rope(krope[:, :, None, :], positions,
                       cfg.rope_theta)[:, :, 0, :]

    if mode == "decode":
        assert cache is not None and pos is not None
        ckv_c = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv, pos, axis=1)
        kr_c = jax.lax.dynamic_update_slice_in_dim(cache["krope"], krope, pos, axis=1)
        new_cache = {"ckv": ckv_c, "krope": kr_c}
        ckv_all, krope_all = ckv_c, kr_c
        kv_len = pos + S
        q_pos0 = pos
    else:
        new_cache = {"ckv": ckv, "krope": krope} if mode == "prefill" else None
        ckv_all, krope_all = ckv, krope
        kv_len = None
        q_pos0 = 0

    scale = (nope + rope_d) ** -0.5
    Sk = ckv_all.shape[1]
    tpos = jnp.arange(Sk)
    neg = jnp.finfo(jnp.float32).min
    w_uk = p["wkv_b"][..., :nope]          # (lora, H, nope)
    w_uv = p["wkv_b"][..., nope:]          # (lora, H, vd)

    if mode != "decode":
        # train/prefill: expand per-head K/V (linear in S) and run the
        # blocked flash path — never materializes (S,S) scores.
        from repro.kernels.blocked import blocked_attention
        k_nope = jnp.einsum("btl,lhn->bthn", ckv_all, w_uk)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope_all[:, :, None, :],
                                      (*k_nope.shape[:3], rope_d))], axis=-1)
        k_full = shard_constraint(k_full, "batch", None, "heads", None)
        v_full = jnp.einsum("btl,lhv->bthv", ckv_all, w_uv)
        v_full = shard_constraint(v_full, "batch", None, "heads", None)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = blocked_attention(q_full, k_full, v_full, True, scale)
        out = shard_constraint(out, "batch", None, "heads", None)
        y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
        return y, new_cache

    if absorb:
        # fold W_UK into the query; score directly against the latent cache
        q_lat = jnp.einsum("bshn,lhn->bshl", q_nope, w_uk)
        scores = (jnp.einsum("bshl,btl->bhst", q_lat, ckv_all) +
                  jnp.einsum("bshr,btr->bhst", q_rope, krope_all))
    else:
        k_nope = jnp.einsum("btl,lhn->bthn", ckv_all, w_uk)
        scores = (jnp.einsum("bshn,bthn->bhst", q_nope, k_nope) +
                  jnp.einsum("bshr,btr->bhst", q_rope, krope_all))
    scores = scores.astype(jnp.float32) * scale
    if mode != "decode" or True:  # causal always (decode masks cache tail too)
        spos = q_pos0 + jnp.arange(S)
        mask = spos[:, None] >= tpos[None, :]
        scores = jnp.where(mask[None, None], scores, neg)
    if kv_len is not None:
        scores = jnp.where((tpos < kv_len)[None, None, None], scores, neg)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)

    if absorb:
        ctx_lat = jnp.einsum("bhst,btl->bshl", probs, ckv_all)
        out = jnp.einsum("bshl,lhv->bshv", ctx_lat, w_uv)
    else:
        vfull = jnp.einsum("btl,lhv->bthv", ckv_all, w_uv)
        out = jnp.einsum("bhst,bthv->bshv", probs, vfull)
    out = shard_constraint(out, "batch", None, "heads", None)
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
    return y, new_cache
