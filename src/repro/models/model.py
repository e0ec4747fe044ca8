"""Public model API.

Pure functions over (config, params, batch):

- ``loss_fn`` / ``forward_hidden`` — training forward.
- ``prefill`` — build a KV/SSM cache from a prompt; returns last-token logits.
- ``decode_step`` — one token for the whole batch against a fixed-size cache.
- ``input_specs`` / ``abstract_cache`` — ShapeDtypeStruct stand-ins for the
  multi-pod dry-run (weak-type-correct, shardable, never allocated).

Batch conventions (all archs):
    tokens  (B, S) int32      labels (B, S) int32 (-1 = masked)
    enc-dec adds enc_embeds (B, S_enc, d_model)  [frontend stub output]
Decode:
    tokens (B, 1) int32, pos () int32, cache pytree.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, FF_NONE, MLA, SSM, ModelConfig,
                                ShapeConfig)
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as tfm
from repro.models.layers import chunked_softmax_xent, rmsnorm
from repro.models.params import (abstract_params, init_params, logical_axes,
                                 param_count, param_specs)
from repro.sharding import shard_constraint

LOSS_CHUNK = 512


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

@jax.named_scope("embed")
def _embed(cfg: ModelConfig, params, tokens):
    x = params["embed"][tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return shard_constraint(x, "batch", "seq", "embed")


def _head_weight(cfg: ModelConfig, params):
    if cfg.tie_embeddings:
        return params["embed"].T          # (D, V)
    return params["lm_head"]


def _head_input(cfg: ModelConfig, x):
    """The final hidden state as the head takes it: the head is linear, so
    dividing its input by ``logits_scaling`` divides the logits."""
    if cfg.logits_scaling != 1.0:
        x = x / cfg.logits_scaling
    return x


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

def forward_hidden(cfg: ModelConfig, params, batch, *, mode: str = "train"):
    """Embeds, runs encoder (if any) + decoder; returns (hidden, aux)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.arange(S)
    enc_out = None
    if cfg.enc_layers:
        enc_in = batch["enc_embeds"].astype(jnp.dtype(cfg.dtype))
        enc_pos = jnp.arange(enc_in.shape[1])
        enc_out = tfm.encoder(cfg, params["encoder"], enc_in,
                              positions=enc_pos, mode=mode)
    x = _embed(cfg, params, tokens)
    x, _, aux = tfm.decoder(cfg, params["decoder"], x, positions=positions,
                            mode=mode, cache=None, pos=None, enc_out=enc_out)
    with jax.named_scope("norm"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


def loss_fn(cfg: ModelConfig, params, batch) -> Tuple[jax.Array, dict]:
    hidden, aux = forward_hidden(cfg, params, batch, mode="train")
    w_head = _head_weight(cfg, params)
    with jax.named_scope("head"):
        loss_sum, weight = chunked_softmax_xent(
            _head_input(cfg, hidden), w_head, batch["labels"],
            chunk=min(LOSS_CHUNK, hidden.shape[1]),
            valid_vocab=cfg.vocab_size)
    xent = loss_sum / jnp.maximum(weight, 1.0)
    loss = xent + aux
    return loss, {"loss": loss, "xent": xent, "aux": aux, "tokens": weight}


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------

def _layer_cache(cfg: ModelConfig, i: int, batch: int, max_len: int, dtype,
                 abstract: bool, enc_len: int = 0):
    mixer = cfg.mixer_at(i)
    c = {}
    if mixer in (ATTN,):
        fn = attn_mod.abstract_kv_cache if abstract else attn_mod.init_kv_cache
        c["kv"] = fn(cfg, batch, max_len, dtype)
    elif mixer == MLA:
        fn = attn_mod.abstract_mla_cache if abstract else attn_mod.init_mla_cache
        c["kv"] = fn(cfg, batch, max_len, dtype)
    elif mixer == SSM:
        fn = ssm_mod.abstract_ssm_cache if abstract else ssm_mod.init_ssm_cache
        c["ssm"] = fn(cfg, batch, dtype)
    if cfg.enc_layers:
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        shape = (batch, enc_len, kv, hd)
        if abstract:
            s = jax.ShapeDtypeStruct(shape, dtype)
            c["cross"] = {"ck": s, "cv": s}
        else:
            c["cross"] = {"ck": jnp.zeros(shape, dtype),
                          "cv": jnp.zeros(shape, dtype)}
    return c


def _stack_cache(leaves: list):
    """list of per-block cache pytrees -> stacked pytree (leading axis)."""
    return jax.tree.map(lambda *xs: (
        jax.ShapeDtypeStruct((len(xs),) + xs[0].shape, xs[0].dtype)
        if isinstance(xs[0], jax.ShapeDtypeStruct)
        else jnp.stack(xs)), *leaves)


def make_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               abstract: bool = False, enc_len: int = 0,
               dtype: Optional[jnp.dtype] = None) -> dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    prefix_n, scan_n = cfg.scan_layers()
    period = cfg.layer_period()
    cache = {}
    if prefix_n:
        cache["prefix"] = {
            f"layer{i}": _layer_cache(cfg, i, batch, max_len, dtype, abstract,
                                      enc_len)
            for i in range(prefix_n)}
    if scan_n:
        n_blocks = scan_n // period
        block = {f"sub{j}": _layer_cache(cfg, prefix_n + j, batch, max_len,
                                         dtype, abstract, enc_len)
                 for j in range(period)}
        cache["blocks"] = _stack_cache([block] * n_blocks)
    return cache


def _layer_cache_axes(cfg: ModelConfig, i: int) -> dict:
    """Logical axes mirroring _layer_cache (for dry-run input shardings)."""
    mixer = cfg.mixer_at(i)
    c = {}
    if mixer == ATTN:
        kv = ("cache_batch", "cache_seq", "kv_heads", None)
        c["kv"] = {"k": kv, "v": kv}
    elif mixer == MLA:
        c["kv"] = {"ckv": ("cache_batch", "cache_seq", None),
                   "krope": ("cache_batch", "cache_seq", None)}
    elif mixer == SSM:
        c["ssm"] = {"conv": ("cache_batch", None, "ssm_inner"),
                    "h": ("cache_batch", "ssm_heads", None, None)}
    if cfg.enc_layers:
        kv = ("cache_batch", None, "kv_heads", None)
        c["cross"] = {"ck": kv, "cv": kv}
    return c


def cache_axes(cfg: ModelConfig) -> dict:
    """Logical-axis tree matching make_cache's structure."""
    prefix_n, scan_n = cfg.scan_layers()
    period = cfg.layer_period()
    axes = {}
    if prefix_n:
        axes["prefix"] = {f"layer{i}": _layer_cache_axes(cfg, i)
                          for i in range(prefix_n)}
    if scan_n:
        block = {f"sub{j}": _layer_cache_axes(cfg, prefix_n + j)
                 for j in range(period)}
        axes["blocks"] = jax.tree.map(
            lambda t: ("layers",) + t, block,
            is_leaf=lambda l: isinstance(l, tuple) and all(
                a is None or isinstance(a, str) for a in l))
    return axes


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def prefill(cfg: ModelConfig, params, batch):
    """Run the prompt; returns (cache_at_prompt_len, last_token_logits).

    The returned KV caches have sequence length == prompt length; the serving
    driver pads them to the serving window before calling decode_step.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    positions = jnp.arange(S)
    enc_out = None
    if cfg.enc_layers:
        enc_in = batch["enc_embeds"].astype(jnp.dtype(cfg.dtype))
        enc_out = tfm.encoder(cfg, params["encoder"], enc_in,
                              positions=jnp.arange(enc_in.shape[1]),
                              mode="prefill")
    x = _embed(cfg, params, tokens)
    x, cache, _ = tfm.decoder(cfg, params["decoder"], x, positions=positions,
                              mode="prefill", cache=None, pos=None,
                              enc_out=enc_out)
    x = _head_input(cfg, rmsnorm(x, params["final_norm"], cfg.norm_eps))
    logits = jnp.einsum("bd,dv->bv", x[:, -1, :], _head_weight(cfg, params))
    logits = shard_constraint(logits, "batch", "vocab")
    return cache, logits[:, :cfg.vocab_size].astype(jnp.float32)


def decode_step(cfg: ModelConfig, params, cache, tokens, pos):
    """One decode step. tokens: (B,1) int32; pos: () int32 current length."""
    positions = pos + jnp.arange(1)
    x = _embed(cfg, params, tokens)
    x, new_cache, _ = tfm.decoder(cfg, params["decoder"], x,
                                  positions=positions, mode="decode",
                                  cache=cache, pos=pos, enc_out=None)
    x = _head_input(cfg, rmsnorm(x, params["final_norm"], cfg.norm_eps))
    logits = jnp.einsum("bsd,dv->bsv", x, _head_weight(cfg, params))
    logits = shard_constraint(logits, "batch", None, "vocab")
    return logits[:, 0, :cfg.vocab_size].astype(jnp.float32), new_cache


def pad_cache(cfg: ModelConfig, cache, prompt_len: int, max_len: int):
    """Grow prefill KV caches (seq dim == prompt_len) to the serving window.

    Only self-attention KV leaves (under a ``kv`` key) are padded; SSM states,
    conv windows, and cross-attention KV keep their shapes.  Leaves under
    ``blocks`` carry a leading stacked-layers axis, shifting the seq axis by 1.
    """
    if max_len == prompt_len:
        return cache

    def _pad_leaf(path, x):
        names = [str(getattr(p, "key", "")) for p in path]
        if "kv" not in names:
            return x
        axis = 2 if "blocks" in names else 1
        if x.shape[axis] != prompt_len:
            return x
        pad_width = [(0, 0)] * x.ndim
        pad_width[axis] = (0, max_len - prompt_len)
        return jnp.pad(x, pad_width)

    return jax.tree_util.tree_map_with_path(_pad_leaf, cache)


# ---------------------------------------------------------------------------
# Dry-run input specs
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    dt = jnp.dtype(cfg.dtype)
    if shape.kind == "train":
        spec = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.enc_layers:
            spec["enc_embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
        return spec
    if shape.kind == "prefill":
        spec = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.enc_layers:
            spec["enc_embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
        return spec
    assert shape.kind == "decode"
    return {
        "tokens": jax.ShapeDtypeStruct((B, 1), i32),
        "pos": jax.ShapeDtypeStruct((), i32),
        "cache": make_cache(cfg, B, S, abstract=True,
                            enc_len=S if cfg.enc_layers else 0),
    }


__all__ = [
    "loss_fn", "forward_hidden", "prefill", "decode_step", "make_cache",
    "input_specs", "init_params", "abstract_params", "logical_axes",
    "param_specs", "param_count", "pad_cache",
]
