"""Mixture-of-experts layer.

``moe_forward`` dispatches by gather: capacity-bounded dispatch via sort +
gather into an (E, C, D) buffer, grouped einsum per expert, and scatter-add
combine.  FLOPs ~ active compute x capacity_factor.  Pure GSPMD-friendly ops
(sort/gather/einsum/scatter) — the expert axis shards over 'model' (expert
parallelism), and XLA inserts the token exchange collectives.

``moe_dense`` is the reference it is tested against: the same router and
top-k, every expert computing every token, combined by the top-k weights.
Exact (no token dropping); FLOPs = num_experts/top_k x the active compute.
Only tests call it.

Expert weights are stacked (E, D, F); the layer is fully differentiable.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import FF_SWIGLU, ModelConfig
from repro.models.layers import apply_ffn
from repro.sharding import shard_constraint


def router_probs(p: dict, x) -> jax.Array:
    """x: (B,S,D) -> fp32 probs (B,S,E)."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    return jax.nn.softmax(logits, axis=-1)


def load_balance_loss(probs, expert_ids, num_experts: int) -> jax.Array:
    """Switch-transformer aux loss: E * sum_e f_e * P_e (fp32 scalar).

    Counts via per-row bincount — a (B*S*k, E) one-hot would cost 4 GB on
    deepseek train_4k."""
    pe = jnp.mean(probs.reshape(-1, num_experts), axis=0)
    B = expert_ids.shape[0]
    ids2 = expert_ids.reshape(B, -1)
    counts = jax.vmap(
        lambda e: jnp.bincount(e, length=num_experts))(ids2)
    counts = jnp.sum(counts.astype(jnp.float32), axis=0)
    fe = counts / jnp.maximum(counts.sum(), 1.0)
    return num_experts * jnp.sum(fe * pe)


def _expert_ffn_batched(xg, p, ff_kind: str):
    """xg: (B, E, C, D) grouped tokens -> (B, E, C, D)."""
    if ff_kind == FF_SWIGLU:
        g = jnp.einsum("becd,edf->becf", xg, p["w_gate"])
        u = jnp.einsum("becd,edf->becf", xg, p["w_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xg.dtype) * u
    else:
        u = jnp.einsum("becd,edf->becf", xg, p["w_up"])
        h = jax.nn.gelu(u.astype(jnp.float32)).astype(xg.dtype)
    h = shard_constraint(h, "batch", "experts", "expert_cap", "expert_ffn")
    return jnp.einsum("becf,efd->becd", h, p["w_down"])


def _dense_experts(cfg: ModelConfig, p: dict, x, weights, ids):
    """All-experts path: (B,S,E) combine weights, exact."""
    m = cfg.moe
    comb = jnp.sum(jax.nn.one_hot(ids, m.num_experts, dtype=jnp.float32)
                   * weights[..., None].astype(jnp.float32), axis=2)  # (B,S,E)
    if m.ff_kind == FF_SWIGLU:
        g = jnp.einsum("bsd,edf->bsef", x, p["w_gate"])
        u = jnp.einsum("bsd,edf->bsef", x, p["w_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        u = jnp.einsum("bsd,edf->bsef", x, p["w_up"])
        h = jax.nn.gelu(u.astype(jnp.float32)).astype(x.dtype)
    y = jnp.einsum("bsef,efd->bsed", h, p["w_down"])
    return jnp.einsum("bsed,bse->bsd", y, comb.astype(x.dtype))


def _moe_gather(cfg: ModelConfig, p: dict, x, weights, ids):
    """Capacity-bounded dispatch, *per sequence* (GShard-style groups).

    Routing/sort/scatter happen independently per batch row, so under GSPMD
    the whole dispatch shards over ('data' on batch, 'model' on experts) with
    no global collectives — the only cross-shard traffic is the token
    exchange implied by the gather (batch-sharded x -> expert-sharded xg),
    which XLA lowers to the all-to-all-like pattern expert parallelism needs.
    Per-sequence capacity C = ceil(S * k * capacity_factor / E).
    """
    m = cfg.moe
    B, S, D = x.shape
    E, k = m.num_experts, m.experts_per_token
    N = S * k

    exp_ids = ids.reshape(B, N).astype(jnp.int32)                 # (B, N)
    w_flat = weights.reshape(B, N)
    tok_ids = jnp.repeat(jnp.arange(S, dtype=jnp.int32), k)[None, :]  # (1, N)
    tok_ids = jnp.broadcast_to(tok_ids, (B, N))

    # per-row stable sort by expert; position-within-expert via group starts
    order = jnp.argsort(exp_ids, axis=-1, stable=True)            # (B, N)
    exp_sorted = jnp.take_along_axis(exp_ids, order, axis=-1)
    onehot_counts = jax.vmap(lambda e: jnp.bincount(e, length=E))(exp_ids)
    starts = jnp.cumsum(onehot_counts, axis=-1) - onehot_counts   # (B, E)
    pos_sorted = jnp.arange(N, dtype=jnp.int32)[None, :] - \
        jnp.take_along_axis(starts, exp_sorted, axis=-1).astype(jnp.int32)
    # un-sort the positions back to assignment order
    pos = jnp.zeros((B, N), jnp.int32).at[
        jnp.arange(B)[:, None], order].set(pos_sorted)

    cap = int(max(4, -(-N * m.capacity_factor // E)))             # ceil
    cap = min(cap, S)
    valid = pos < cap
    scatter_pos = jnp.where(valid, pos, cap)                      # cap = OOB
    bidx = jnp.arange(B)[:, None]

    # expert-parallel padding: when E doesn't divide the 'experts' mesh axes
    # (granite: 40 experts on a 16-way axis), pad the dispatch AND the expert
    # weights to the next multiple so the (B,E,C,D) tensors shard.  Padded
    # experts hold only sentinel slots and zero weights. (§Perf: the
    # unsharded dispatch cost 4 GB/buffer on granite train_4k.)
    from repro.sharding import rule_axis_size
    ep = rule_axis_size("experts")
    E_pad = -(-E // ep) * ep if ep > 1 else E
    p_eff = p
    if E_pad != E:
        padw = ((0, E_pad - E), (0, 0), (0, 0))
        p_eff = dict(p)
        for kname in ("w_gate", "w_up", "w_down"):
            if kname in p:
                p_eff[kname] = jnp.pad(p[kname], padw)

    idx = jnp.full((B, E_pad, cap), S, jnp.int32)                 # S = sentinel
    idx = idx.at[bidx, exp_ids, scatter_pos].set(tok_ids, mode="drop")
    wtab = jnp.zeros((B, E_pad, cap), w_flat.dtype)
    wtab = wtab.at[bidx, exp_ids, scatter_pos].set(w_flat, mode="drop")
    idx = shard_constraint(idx, "batch", "experts", "expert_cap")
    wtab = shard_constraint(wtab, "batch", "experts", "expert_cap")

    # gather via clamp+mask — a sentinel row (concatenate to S+1) makes the
    # seq dim indivisible and GSPMD replicates the FULL global batch in f32
    # (21.5 GB/device/buffer on deepseek train_4k)
    idx_flat = idx.reshape(B, E_pad * cap)
    occupied = idx_flat < S                                       # (B, E*C)
    idx_safe = jnp.minimum(idx_flat, S - 1)
    xg = jnp.take_along_axis(x, idx_safe[:, :, None], axis=1)
    xg = jnp.where(occupied[:, :, None], xg, 0).reshape(B, E_pad, cap, D)
    xg = shard_constraint(xg, "batch", "experts", "expert_cap", "embed")
    yg = _expert_ffn_batched(xg, p_eff, m.ff_kind)                # (B,E,C,D)
    yg = yg * wtab[..., None].astype(yg.dtype)
    # scatter-add combine; masked entries contribute zeros at row S-1
    yg_flat = jnp.where(occupied[:, :, None],
                        yg.reshape(B, E_pad * cap, D), 0)
    y = jnp.zeros((B, S, D), x.dtype).at[bidx, idx_safe, :].add(yg_flat)
    return y


def _moe(cfg: ModelConfig, p: dict, x, experts):
    m = cfg.moe
    probs = router_probs(p, x)                                        # fp32
    weights, ids = jax.lax.top_k(probs, m.experts_per_token)          # (B,S,k)
    weights = (weights / jnp.maximum(
        weights.sum(-1, keepdims=True), 1e-9)).astype(x.dtype)
    aux = load_balance_loss(probs, ids, m.num_experts) * m.router_aux_weight
    y = experts(cfg, p, x, weights, ids)
    if m.num_shared_experts:
        y = y + apply_ffn(p["shared"], x, m.ff_kind)
    return y, aux


def moe_forward(cfg: ModelConfig, p: dict, x) -> Tuple[jax.Array, jax.Array]:
    """Returns (y, aux_loss). x: (B,S,D)."""
    return _moe(cfg, p, x, _moe_gather)


def moe_dense(cfg: ModelConfig, p: dict, x) -> Tuple[jax.Array, jax.Array]:
    """``moe_forward`` with every expert computing every token, so no token
    is dropped: the reference that tests hold the gather dispatch to.
    Returns (y, aux_loss)."""
    return _moe(cfg, p, x, _dense_experts)
