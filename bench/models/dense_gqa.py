"""Plain reference of a llama-architecture decoder (yi-6b): RMSNorm, grouped
query attention with rotary positions, SwiGLU feed-forward, untied head.

Written from the published description in straightforward ``jax.numpy``;
it imports nothing of the program.  ``param_shapes`` is the parameter layout
that the program's trainer holds (leaves stacked over layers), which the
harness checks against the trainer before it installs the weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

VOCAB_PAD = 256          # the program pads its embedding rows to this
BLOCK = "decoder/blocks/sub0/"


def padded_vocab(c: dict) -> int:
    return -(-c["vocab_size"] // VOCAB_PAD) * VOCAB_PAD


def param_shapes(c: dict) -> dict:
    L, d, H, KV = c["num_layers"], c["d_model"], c["num_heads"], c["num_kv_heads"]
    hd, F, V = c["head_dim"], c["d_ff"], padded_vocab(c)
    return {
        "embed": (V, d), "final_norm": (d,), "lm_head": (d, V),
        BLOCK + "mixer_norm": (L, d), BLOCK + "ff_norm": (L, d),
        BLOCK + "mixer/wq": (L, d, H, hd), BLOCK + "mixer/wk": (L, d, KV, hd),
        BLOCK + "mixer/wv": (L, d, KV, hd), BLOCK + "mixer/wo": (L, H, hd, d),
        BLOCK + "ff/w_gate": (L, d, F), BLOCK + "ff/w_up": (L, d, F),
        BLOCK + "ff/w_down": (L, F, d),
    }


def init_rule(path: str, shape: tuple) -> tuple:
    leaf = path.rsplit("/", 1)[-1]
    if leaf.endswith("norm"):
        return ("ones",)
    if leaf == "embed":
        fan_in = shape[1]
    elif leaf == "lm_head":
        fan_in = shape[0]
    elif leaf == "wo":
        fan_in = shape[1] * shape[2]
    else:                               # (layers, fan_in, ...)
        fan_in = shape[1]
    return ("normal", fan_in ** -0.5)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, heads, hd); rotate the two halves by position angles."""
    S, _, hd = x.shape
    freqs = theta ** -(jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None] * freqs)[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(c, lp, x):
    """One decoder layer on one sequence x: (S, d)."""
    eps, H, KV = c["norm_eps"], c["num_heads"], c["num_kv_heads"]
    S, hd = x.shape[0], c["head_dim"]
    h = _rmsnorm(x, lp["mixer_norm"], eps)
    q = _rope(jnp.einsum("sd,dhk->shk", h, lp["wq"]), c["rope_theta"])
    k = _rope(jnp.einsum("sd,dhk->shk", h, lp["wk"]), c["rope_theta"])
    v = jnp.einsum("sd,dhk->shk", h, lp["wv"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("shk,thk->hst", q, k) * jnp.asarray(hd ** -0.5, x.dtype)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, jnp.asarray(-jnp.inf, s.dtype))
    a = jnp.einsum("hst,thk->shk", jax.nn.softmax(s, axis=-1), v)
    x = x + jnp.einsum("shk,hkd->sd", a, lp["wo"])
    h = _rmsnorm(x, lp["ff_norm"], eps)
    g = jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])
    return x + g @ lp["w_down"]


LAYER_KEYS = {"mixer_norm": "mixer_norm", "ff_norm": "ff_norm",
              "mixer/wq": "wq", "mixer/wk": "wk", "mixer/wv": "wv",
              "mixer/wo": "wo", "ff/w_gate": "w_gate", "ff/w_up": "w_up",
              "ff/w_down": "w_down"}


def row_loss_sum(c: dict, p: dict, tokens, labels, dtype=jnp.float32):
    """Sum of the next-token cross-entropy over one row (S,) of tokens."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    V = c["vocab_size"]
    x = p["embed"][tokens]
    layer = jax.checkpoint(lambda lp, x: _layer(c, lp, x))
    for i in range(c["num_layers"]):
        x = layer({n: p[BLOCK + k][i] for k, n in LAYER_KEYS.items()}, x)
    x = _rmsnorm(x, p["final_norm"], c["norm_eps"])
    logits = x @ p["lm_head"][:, :V]
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum((lse - tgt).astype(jnp.float32))


def step_flops(c: dict, batch: int, seq: int) -> float:
    """Operations a forward and backward pass need: 3x the forward's matmul
    FLOPs, causal attention over the S(S+1)/2 pairs it uses, no recompute."""
    d, H, KV, hd, F = (c["d_model"], c["num_heads"], c["num_kv_heads"],
                       c["head_dim"], c["d_ff"])
    layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
    matmul = 2 * (c["num_layers"] * layer + d * c["vocab_size"]) * seq
    pairs = seq * (seq + 1) // 2
    attn = c["num_layers"] * 2 * 2 * pairs * H * hd     # QK^T and AV
    return 3.0 * batch * (matmul + attn)
