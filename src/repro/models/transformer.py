"""Decoder/encoder stacks.

Homogeneous runs of layers execute under ``jax.lax.scan`` over stacked params
(period-k blocks for hybrids like Jamba), keeping HLO size and compile time
bounded at 60-layer/512-device scale.  Training remats each scanned block.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, FF_MOE, FF_NONE, MLA, SSM, ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import rmsnorm
from repro.sharding import shard_constraint

_REMAT = {"policy": "full"}   # none | full | dots  (§Perf knob)
_MLA_ABSORB = {"decode": True, "prefill": False, "train": False}
_SCAN = {"unroll": False}     # True: unroll layer scan (cost-composition lowers)


def set_remat(policy: str):
    assert policy in ("none", "full", "dots")
    _REMAT["policy"] = policy


def set_scan_unroll(unroll: bool):
    _SCAN["unroll"] = unroll


def set_mla_absorb(mode: str, value: bool):
    _MLA_ABSORB[mode] = value


def _maybe_remat(fn, mode: str):
    if mode != "train" or _REMAT["policy"] == "none":
        return fn
    if _REMAT["policy"] == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Single layer
# ---------------------------------------------------------------------------

def _branch(cfg: ModelConfig, y):
    """A residual branch's output as it is added to the stream."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return y


def apply_layer(cfg: ModelConfig, p: dict, x, layer_idx: int, *, positions,
                mode: str, cache: Optional[dict], pos, enc_out):
    """Returns (x, new_cache, aux_loss)."""
    mixer = cfg.mixer_at(layer_idx)
    ff = cfg.ff_at(layer_idx)
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    c_in = cache or {}

    with jax.named_scope("norm"):
        h = rmsnorm(x, p["mixer_norm"], cfg.norm_eps)
    with jax.named_scope("mixer"):
        if mixer == ATTN:
            y, kvc = attn_mod.attn_forward(
                cfg, p["mixer"], h, positions=positions, mode=mode,
                cache=c_in.get("kv"), pos=pos, causal=True)
            new_cache["kv"] = kvc
        elif mixer == MLA:
            y, kvc = attn_mod.mla_forward(
                cfg, p["mixer"], h, positions=positions, mode=mode,
                cache=c_in.get("kv"), pos=pos, absorb=_MLA_ABSORB[mode])
            new_cache["kv"] = kvc
        elif mixer == SSM:
            y, sc = ssm_mod.ssm_forward(cfg, p["mixer"], h, mode=mode,
                                        cache=c_in.get("ssm"))
            new_cache["ssm"] = sc
        else:
            raise ValueError(mixer)
    x = x + _branch(cfg, y)
    x = shard_constraint(x, "batch", "seq", "embed")

    if "cross" in p:
        with jax.named_scope("norm"):
            h = rmsnorm(x, p["cross_norm"], cfg.norm_eps)
        if mode == "decode":
            ck = c_in["cross"]
            kv = (ck["ck"], ck["cv"])
            new_cache["cross"] = ck
        else:
            k = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross"]["wk"])
            v = jnp.einsum("bsd,dhk->bshk", enc_out, p["cross"]["wv"])
            kv = (k, v)
            if mode == "prefill":
                new_cache["cross"] = {"ck": k, "cv": v}
        y, _ = attn_mod.attn_forward(
            cfg, p["cross"], h, positions=positions, mode=mode,
            kv_override=kv, causal=False)
        x = x + _branch(cfg, y)
        x = shard_constraint(x, "batch", "seq", "embed")

    if ff != FF_NONE:
        with jax.named_scope("norm"):
            h = rmsnorm(x, p["ff_norm"], cfg.norm_eps)
        if ff == FF_MOE:
            with jax.named_scope("experts"):
                y, aux = moe_mod.moe_forward(cfg, p["ff"], h)
        else:
            from repro.models.layers import apply_ffn
            with jax.named_scope("ffn"):
                y = apply_ffn(p["ff"], h, ff)
        x = x + _branch(cfg, y)
        x = shard_constraint(x, "batch", "seq", "embed")

    new_cache = {k: v for k, v in new_cache.items() if v is not None}
    return x, (new_cache or None), aux


# ---------------------------------------------------------------------------
# Decoder stack (prefix loop + scanned blocks)
# ---------------------------------------------------------------------------

def decoder(cfg: ModelConfig, dparams: dict, x, *, positions, mode: str,
            cache: Optional[dict], pos, enc_out=None):
    prefix_n, scan_n = cfg.scan_layers()
    period = cfg.layer_period()
    aux_total = jnp.zeros((), jnp.float32)
    new_cache = {}

    def prefix_fn(i):
        def fn(x, lp, c):
            return apply_layer(cfg, lp, x, i, positions=positions, mode=mode,
                               cache=c, pos=pos, enc_out=enc_out)
        # a hybrid's layers are rematted one by one, here as in the scan
        return _maybe_remat(fn, mode) if period > 1 else fn

    if prefix_n:
        new_cache["prefix"] = {}
        for i in range(prefix_n):
            name = f"layer{i}"
            c = cache["prefix"][name] if cache else None
            x, nc, aux = prefix_fn(i)(x, dparams["prefix"][name], c)
            aux_total = aux_total + aux
            if nc is not None:
                new_cache["prefix"][name] = nc
        if not new_cache["prefix"]:
            del new_cache["prefix"]

    if scan_n:
        # hybrids (period > 1) remat each SUB-layer: rematting the whole
        # 8-layer Jamba block keeps all 8 layers' intermediates live during
        # its backward (150 GB/chip before this — EXPERIMENTS.md §Perf)
        def sub_fn(x, lp, c, j):
            return apply_layer(cfg, lp, x, prefix_n + j, positions=positions,
                               mode=mode, cache=c, pos=pos, enc_out=enc_out)

        if period > 1:
            # close over the static sub-layer index (it selects layer kind)
            sub_fns = [_maybe_remat(
                (lambda j: lambda x, lp, c: sub_fn(x, lp, c, j))(j), mode)
                for j in range(period)]
        else:
            sub_fns = [lambda x, lp, c: sub_fn(x, lp, c, 0)]

        def block_fn(x, block_params, block_cache):
            block_new_cache = {}
            aux_b = jnp.zeros((), jnp.float32)
            for j in range(period):
                name = f"sub{j}"
                c = block_cache[name] if block_cache else None
                x, nc, aux = sub_fns[j](x, block_params[name], c)
                aux_b = aux_b + aux
                if nc is not None:
                    block_new_cache[name] = nc
            return x, (block_new_cache or None), aux_b

        if period == 1:
            block_fn = _maybe_remat(block_fn, mode)

        def scan_body(carry, xs):
            x, aux_acc = carry
            bp, bc = xs
            x, bnc, aux_b = block_fn(x, bp, bc)
            return (x, aux_acc + aux_b), bnc

        bc0 = cache["blocks"] if cache else None
        unroll = (scan_n // period) if _SCAN["unroll"] else 1
        if bc0 is None:
            (x, aux_total), blocks_cache = jax.lax.scan(
                lambda c, bp: scan_body(c, (bp, None)),
                (x, aux_total), dparams["blocks"], unroll=unroll)
        else:
            (x, aux_total), blocks_cache = jax.lax.scan(
                scan_body, (x, aux_total), (dparams["blocks"], bc0),
                unroll=unroll)
        if blocks_cache is not None:
            new_cache["blocks"] = blocks_cache

    return x, (new_cache or None), aux_total


# ---------------------------------------------------------------------------
# Encoder stack (bidirectional, scanned)
# ---------------------------------------------------------------------------

def encoder(cfg: ModelConfig, eparams: dict, x, *, positions, mode: str):
    def layer_fn(x, lp):
        h = rmsnorm(x, lp["mixer_norm"], cfg.norm_eps)
        y, _ = attn_mod.attn_forward(cfg, lp["mixer"], h, positions=positions,
                                     mode="train", causal=False)
        x = x + y
        h = rmsnorm(x, lp["ff_norm"], cfg.norm_eps)
        from repro.models.layers import apply_ffn
        x = x + apply_ffn(lp["ff"], h, cfg.ff_kind)
        return shard_constraint(x, "batch", "seq", "embed")

    layer_fn = _maybe_remat(layer_fn, mode)
    n = jax.tree.leaves(eparams["blocks"])[0].shape[0]
    x, _ = jax.lax.scan(lambda c, lp: (layer_fn(c, lp), None),
                        x, eparams["blocks"],
                        unroll=n if _SCAN["unroll"] else 1)
    return rmsnorm(x, eparams["final_norm"], cfg.norm_eps)
