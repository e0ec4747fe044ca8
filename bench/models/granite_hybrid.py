"""Plain reference of granite-4.0-h-micro (``granitemoehybrid``): Mamba-2
mixers with a NoPE grouped-query attention layer at a fixed period, a SwiGLU
MLP after every mixer, and Granite's muP-style scalars.

    x = embed[tokens] * embedding_multiplier
    per layer:  x = x + residual_multiplier * mixer(rmsnorm(x))
                x = x + residual_multiplier * mlp(rmsnorm(x))
    logits = rmsnorm(x) @ embed.T / logits_scaling

The attention layer is a plain causal softmax over ``q k^T *
attention_multiplier`` with no positional embedding; the Mamba-2 mixer is
``bench/models/mamba2.py``'s (the quadratic form of the SSD over the whole
sequence, a different algorithm from the program's chunked scan).  The
embedding multiplier scales only the lookup: the tied head reads the table
unscaled.  That is how the config's keys read; no departure is known.  It
imports nothing of the program.

``param_shapes`` is the program's layout: with ``attn_every`` layers to a
period, the first ``num_layers % attn_every`` layers are held one by one
under ``decoder/prefix/layer<i>``, and the rest stacked by period under
``decoder/blocks/sub<j>``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.models.mamba2 import _rmsnorm, _ssd

VOCAB_PAD = 256
KV_HEADS_PER_BLOCK = 1   # KV heads whose (S, S) scores are live at once


def padded_vocab(c: dict) -> int:
    return -(-c["vocab_size"] // VOCAB_PAD) * VOCAB_PAD


def is_attention(c: dict, i: int) -> bool:
    return i % c["attn_every"] == c["attn_offset"]


def _ssm_dims(c: dict):
    s = c["ssm"]
    di = s["expand"] * c["d_model"]
    H = di // s["head_dim"]
    gn = s["num_groups"] * s["d_state"]
    return di, H, gn, di + 2 * gn


def _layer_shapes(c: dict, i: int) -> dict:
    d, F = c["d_model"], c["d_ff"]
    out = {"mixer_norm": (d,), "ff_norm": (d,), "ff/w_gate": (d, F),
           "ff/w_up": (d, F), "ff/w_down": (F, d)}
    if is_attention(c, i):
        H, KV, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
        out.update({"mixer/wq": (d, H, hd), "mixer/wk": (d, KV, hd),
                    "mixer/wv": (d, KV, hd), "mixer/wo": (H, hd, d)})
    else:
        s = c["ssm"]
        di, H, gn, conv = _ssm_dims(c)
        out.update({"mixer/in_proj": (d, 2 * di + 2 * gn + H),
                    "mixer/conv_w": (s["conv_width"], conv),
                    "mixer/conv_b": (conv,), "mixer/a_log": (H,),
                    "mixer/d_skip": (H,), "mixer/dt_bias": (H,),
                    "mixer/out_norm": (di,), "mixer/out_proj": (di, d)})
    return out


def _where(c: dict, i: int):
    """(path prefix, index in the stack or None) of layer ``i``'s leaves."""
    period, L = c["attn_every"], c["num_layers"]
    n_prefix = L % period
    if i < n_prefix:
        return f"decoder/prefix/layer{i}/", None
    b, j = divmod(i - n_prefix, period)
    return f"decoder/blocks/sub{j}/", b


def param_shapes(c: dict) -> dict:
    period, L = c["attn_every"], c["num_layers"]
    n_blocks = (L - L % period) // period
    out = {"embed": (padded_vocab(c), c["d_model"]),
           "final_norm": (c["d_model"],)}
    for i in range(L):
        at, b = _where(c, i)
        if b is not None and b > 0:
            continue                    # stacked leaves are listed once
        for k, shape in _layer_shapes(c, i).items():
            out[at + k] = shape if b is None else (n_blocks,) + shape
    return out


def init_rule(path: str, shape: tuple) -> tuple:
    leaf = path.rsplit("/", 1)[-1]
    core = shape[1:] if "/blocks/" in path else shape
    if leaf.endswith("norm") or leaf == "d_skip":
        return ("ones",)
    if leaf == "conv_b":
        return ("zeros",)
    if leaf == "a_log":                 # A in [1, 16)
        return ("log_uniform", 1.0, 16.0)
    if leaf == "dt_bias":               # dt in [1e-3, 1e-1]
        return ("softplus_inv_log_uniform", 1e-3, 1e-1)
    if leaf == "conv_w":
        lim = core[0] ** -0.5
        return ("uniform", -lim, lim)
    if leaf == "embed":
        fan_in = shape[1]
    elif leaf == "wo":
        fan_in = core[0] * core[1]
    else:                               # (fan_in, ...)
        fan_in = core[0]
    return ("normal", fan_in ** -0.5)


def _attention(c, lp, h):
    """Causal NoPE GQA on one sequence h: (S, d), a block of KV heads at a
    time so that only its (S, S) scores are live."""
    H, KV, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    S, G, kb = h.shape[0], H // KV, KV_HEADS_PER_BLOCK
    q = jnp.einsum("sd,dhk->shk", h, lp["wq"]).reshape(S, KV // kb, kb * G, hd)
    k = jnp.einsum("sd,dhk->shk", h, lp["wk"]).reshape(S, KV // kb, kb, hd)
    v = jnp.einsum("sd,dhk->shk", h, lp["wv"]).reshape(S, KV // kb, kb, hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    scale = jnp.asarray(c["attention_multiplier"], h.dtype)

    def block(args):
        qb, kb_, vb = args              # (S, kb*G, hd), (S, kb, hd) x2
        kb_ = jnp.repeat(kb_, G, axis=1)
        vb = jnp.repeat(vb, G, axis=1)
        s = jnp.einsum("shk,thk->hst", qb, kb_) * scale
        s = jnp.where(causal, s, jnp.asarray(-jnp.inf, s.dtype))
        return jnp.einsum("hst,thk->shk", jax.nn.softmax(s, axis=-1), vb)

    a = jax.lax.map(jax.checkpoint(block), (
        q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
        v.transpose(1, 0, 2, 3)))       # (KV/kb, S, kb*G, hd)
    a = a.transpose(1, 0, 2, 3).reshape(S, H, hd)
    return jnp.einsum("shk,hkd->sd", a, lp["wo"])


def _mamba(c, lp, h):
    """The Mamba-2 mixer on one sequence h: (S, d)."""
    s, eps = c["ssm"], c["norm_eps"]
    di, H, gn, conv = _ssm_dims(c)
    L, P, W = h.shape[0], s["head_dim"], s["conv_width"]
    G, N = s["num_groups"], s["d_state"]
    zxbcdt = h @ lp["in_proj"]
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + conv],
                  zxbcdt[:, di + conv:])
    pad = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
    xbc = sum(pad[k:k + L] * lp["conv_w"][k] for k in range(W)) + lp["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :di].reshape(L, H, P)
    B = xbc[:, di:di + gn].reshape(L, G, N)
    C = xbc[:, di + gn:].reshape(L, G, N)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    y = _ssd(xs, dt, -jnp.exp(lp["a_log"]), B, C)
    y = (y + lp["d_skip"][None, :, None] * xs).reshape(L, di)
    y = _rmsnorm(y * jax.nn.silu(z), lp["out_norm"], eps)
    return y @ lp["out_proj"]


def _layer(c, i, lp, x):
    """Layer ``i`` on one sequence x: (S, d)."""
    eps, r = c["norm_eps"], c["residual_multiplier"]
    h = _rmsnorm(x, lp["mixer_norm"], eps)
    mix = _attention(c, lp, h) if is_attention(c, i) else _mamba(c, lp, h)
    x = x + r * mix
    h = _rmsnorm(x, lp["ff_norm"], eps)
    g = jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
    return x + r * (g @ lp["w_down"])


def row_loss_sum(c: dict, p: dict, tokens, labels, dtype=jnp.float32):
    """Sum of the next-token cross-entropy over one row (S,) of tokens."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    V = c["vocab_size"]
    x = p["embed"][tokens] * jnp.asarray(c["embedding_multiplier"], dtype)
    for i in range(c["num_layers"]):
        at, b = _where(c, i)
        lp = {k.rsplit("/", 1)[-1]: p[at + k] if b is None else p[at + k][b]
              for k in _layer_shapes(c, i)}
        x = jax.checkpoint(lambda lp, x, i=i: _layer(c, i, lp, x))(lp, x)
    x = _rmsnorm(x, p["final_norm"], c["norm_eps"])
    logits = (x @ p["embed"][:V].T) / jnp.asarray(c["logits_scaling"], dtype)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum((lse - tgt).astype(jnp.float32))


def step_flops(c: dict, batch: int, seq: int) -> float:
    """Operations a forward and backward pass need: 3x the forward's matmul
    FLOPs, causal attention over the S(S+1)/2 pairs it uses, the SSD counted
    as ``mamba2.py`` counts it (its chunked algorithm), no recompute."""
    s, d, F = c["ssm"], c["d_model"], c["d_ff"]
    H, KV, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
    di, Hs, gn, conv = _ssm_dims(c)
    P, N, Q = s["head_dim"], s["d_state"], s["chunk"]
    mlp = 2 * 3 * d * F * seq
    attn = (2 * (2 * d * H * hd + 2 * d * KV * hd) * seq
            + 2 * 2 * (seq * (seq + 1) // 2) * H * hd)     # QK^T and AV
    proj = 2 * d * (2 * di + 2 * gn + Hs) + 2 * di * d + 2 * s["conv_width"] * conv
    pairs = seq // Q * (Q * (Q + 1) // 2)
    mamba = proj * seq + 2 * pairs * (gn + Hs * P) + 4 * seq * Hs * P * N
    layers = sum(mlp + (attn if is_attention(c, i) else mamba)
                 for i in range(c["num_layers"]))
    return 3.0 * batch * (layers + 2 * d * c["vocab_size"] * seq)
