"""Plain reference of Mamba-2 (arXiv:2405.21060): RMSNorm, then the SSD
block (input projection to z, x, B, C, dt; depthwise causal convolution;
the state-space recurrence; gated RMSNorm; output projection), tied head.

The recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T, y_t = C_t h_t + D x_t
is computed in its quadratic form over the whole sequence:
y_t = sum_{s<=t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s, with cum the running
sum of dt A.  That is a different algorithm from the program's chunked scan,
so the two agree only if both are right.  It imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

VOCAB_PAD = 256
BLOCK = "decoder/blocks/sub0/"


def padded_vocab(c: dict) -> int:
    return -(-c["vocab_size"] // VOCAB_PAD) * VOCAB_PAD


def _dims(c: dict):
    s = c["ssm"]
    di = s["expand"] * c["d_model"]
    H = di // s["head_dim"]
    gn = s["num_groups"] * s["d_state"]
    return di, H, gn, di + 2 * gn


def param_shapes(c: dict) -> dict:
    L, d, s = c["num_layers"], c["d_model"], c["ssm"]
    di, H, gn, conv = _dims(c)
    m = BLOCK + "mixer/"
    return {
        "embed": (padded_vocab(c), d), "final_norm": (d,),
        BLOCK + "mixer_norm": (L, d),
        m + "in_proj": (L, d, 2 * di + 2 * gn + H),
        m + "conv_w": (L, s["conv_width"], conv), m + "conv_b": (L, conv),
        m + "a_log": (L, H), m + "d_skip": (L, H), m + "dt_bias": (L, H),
        m + "out_norm": (L, di), m + "out_proj": (L, di, d),
    }


def init_rule(path: str, shape: tuple) -> tuple:
    leaf = path.rsplit("/", 1)[-1]
    if leaf.endswith("norm") or leaf == "d_skip":
        return ("ones",)
    if leaf == "conv_b":
        return ("zeros",)
    if leaf == "a_log":                 # A in [1, 16)
        return ("log_uniform", 1.0, 16.0)
    if leaf == "dt_bias":               # dt in [1e-3, 1e-1]
        return ("softplus_inv_log_uniform", 1e-3, 1e-1)
    if leaf == "conv_w":
        lim = shape[1] ** -0.5
        return ("uniform", -lim, lim)
    fan_in = shape[1]                   # embed (V, d); (layers, fan_in, out)
    return ("normal", fan_in ** -0.5)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


HEADS_PER_BLOCK = 8      # heads whose (L, L) decay is live at once


def _ssd(x, dt, A, B, C):
    """x: (L,H,P); dt: (L,H); A: (H,); B, C: (L,G,N) -> y: (L,H,P)."""
    L, H, P = x.shape
    hb = min(HEADS_PER_BLOCK, H)
    nb = H // hb
    cb = jnp.einsum("tgn,sgn->tsg", C, B)                      # (t,s,G)
    causal = jnp.tril(jnp.ones((L, L), bool))[:, :, None]
    group = jnp.arange(H) // (H // B.shape[1])

    def heads(args):
        xb, dtb, ab, gb = args                                # hb heads
        cum = jnp.cumsum(dtb * ab, axis=0)                    # (L,hb)
        diff = cum[:, None, :] - cum[None, :, :]              # (t,s,hb)
        decay = jnp.exp(jnp.where(causal, diff,
                                  jnp.asarray(-jnp.inf, x.dtype)))
        return jnp.einsum("tsh,sh,shp->thp", decay * cb[:, :, gb], dtb, xb)

    ys = jax.lax.map(jax.checkpoint(heads), (
        x.reshape(L, nb, hb, P).transpose(1, 0, 2, 3),
        dt.reshape(L, nb, hb).transpose(1, 0, 2),
        A.reshape(nb, hb), group.reshape(nb, hb)))
    return ys.transpose(1, 0, 2, 3).reshape(L, H, P)


def _layer(c, lp, x):
    s, eps = c["ssm"], c["norm_eps"]
    di, H, gn, conv = _dims(c)
    L, P, W = x.shape[0], s["head_dim"], s["conv_width"]
    G, N = s["num_groups"], s["d_state"]
    zxbcdt = _rmsnorm(x, lp["mixer_norm"], eps) @ lp["in_proj"]
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
    return x + y @ lp["out_proj"]


LAYER_KEYS = ("mixer_norm", "mixer/in_proj", "mixer/conv_w", "mixer/conv_b",
              "mixer/a_log", "mixer/d_skip", "mixer/dt_bias",
              "mixer/out_norm", "mixer/out_proj")


def row_loss_sum(c: dict, p: dict, tokens, labels, dtype=jnp.float32):
    """Sum of the next-token cross-entropy over one row (S,) of tokens."""
    p = {k: v.astype(dtype) for k, v in p.items()}
    V = c["vocab_size"]
    x = p["embed"][tokens]
    layer = jax.checkpoint(lambda lp, x: _layer(c, lp, x))
    for i in range(c["num_layers"]):
        x = layer({k.rsplit("/", 1)[-1]: p[BLOCK + k][i] for k in LAYER_KEYS},
                  x)
    x = _rmsnorm(x, p["final_norm"], c["norm_eps"])
    logits = x @ p["embed"][:V].T
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.sum((lse - tgt).astype(jnp.float32))


def step_flops(c: dict, batch: int, seq: int) -> float:
    """Operations a forward and backward pass need: 3x the forward's, with
    the SSD counted as its chunked algorithm (causal pairs within a chunk,
    state in and out per token), no recompute."""
    s, d = c["ssm"], c["d_model"]
    di, H, gn, conv = _dims(c)
    P, N, Q = s["head_dim"], s["d_state"], s["chunk"]
    proj = 2 * d * (2 * di + 2 * gn + H) + 2 * di * d + 2 * s["conv_width"] * conv
    pairs = seq // Q * (Q * (Q + 1) // 2)
    ssd = 2 * pairs * (gn + H * P) + 4 * seq * H * P * N
    fwd = c["num_layers"] * (proj * seq + ssd) + 2 * d * c["vocab_size"] * seq
    return 3.0 * batch * fwd
