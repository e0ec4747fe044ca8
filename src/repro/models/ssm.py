"""Mamba-2 SSD (state-space duality) block.

The SSD recurrence per head (state N, head dim P):
    h_t = a_t * h_{t-1} + dt_t * (B_t outer x_t)     h in R^{P x N}
    y_t = h_t @ C_t + D * x_t                        a_t = exp(A * dt_t), A < 0

Training/prefill uses the *chunked* algorithm: quadratic attention-like
computation inside chunks of Q tokens (MXU-friendly) plus a cheap inter-chunk
state recurrence — this is the TPU-native adaptation of the paper's GPU scan
(DESIGN.md §2).  ``repro.kernels.ssd_scan`` is the Pallas version of the
chunked core; this module is the jnp path (identical math) used on CPU and by
the dry-run.

Decode keeps (conv window, h state) per layer in the cache pytree.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import gated_rmsnorm
from repro.sharding import shard_constraint


def _dims(cfg: ModelConfig):
    ss = cfg.ssm
    d_inner = ss.expand * cfg.d_model
    nh = ss.num_heads or d_inner // ss.head_dim
    gn = ss.num_groups * ss.d_state
    conv_dim = d_inner + 2 * gn
    return ss, d_inner, nh, gn, conv_dim


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype) -> dict:
    ss, d_inner, nh, gn, conv_dim = _dims(cfg)
    return {
        "conv": jnp.zeros((batch, ss.conv_width - 1, conv_dim), dtype),
        "h": jnp.zeros((batch, nh, ss.head_dim, ss.d_state), jnp.float32),
    }


def abstract_ssm_cache(cfg: ModelConfig, batch: int, dtype) -> dict:
    ss, d_inner, nh, gn, conv_dim = _dims(cfg)
    return {
        "conv": jax.ShapeDtypeStruct((batch, ss.conv_width - 1, conv_dim), dtype),
        "h": jax.ShapeDtypeStruct((batch, nh, ss.head_dim, ss.d_state), jnp.float32),
    }


def _causal_conv(u, w, b):
    """Depthwise causal conv. u: (B,L,C); w: (W,C); b: (C,)."""
    W = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    y = jnp.zeros_like(u)
    for i in range(W):  # W == 4: unrolled shifts beat conv_general on TPU
        y = y + pad[:, i:i + u.shape[1], :] * w[i]
    return y + b


def ssd_chunked(x, dt, a_log, b, c, *, chunk: int):
    """Chunked SSD (jnp path; same math as kernels/ssd_scan.py).

    x: (B,L,H,P)  dt: (B,L,H) post-softplus  a_log: (H,)
    b, c: (B,L,G,N) with G dividing H.  Returns y: (B,L,H,P).

    Chunks are processed by a sequential ``lax.scan`` carrying the (B,H,P,N)
    state — only ONE chunk's quadratic (B,Q,Q,H) tensors are ever live
    (materializing all chunks at once costs O(L*Q) memory: 34 TB global on
    mamba2 train_4k — see EXPERIMENTS.md §Perf).  The chunk body is
    checkpointed so the backward pass recomputes those tensors per chunk.
    """
    from repro.models.transformer import _SCAN  # unroll flag (cost lowers)

    B, L, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)
    nc = L // Q

    A = -jnp.exp(a_log.astype(jnp.float32))                  # (H,) negative
    f32 = jnp.float32
    # (nc, B, Q, ...) scan layout
    xc = x.astype(f32).reshape(B, nc, Q, H, P).swapaxes(0, 1)
    dtc = dt.astype(f32).reshape(B, nc, Q, H).swapaxes(0, 1)
    bc = b.astype(f32).reshape(B, nc, Q, G, N).swapaxes(0, 1)
    cc = c.astype(f32).reshape(B, nc, Q, G, N).swapaxes(0, 1)
    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def body(h_prev, inp):
        xq, dtq, bq, cq = inp           # (B,Q,H,P) (B,Q,H) (B,Q,G,N) (B,Q,G,N)
        # group->head expansion erases sharding; re-constrain onto heads
        bh = shard_constraint(jnp.repeat(bq, rep, axis=2),
                              "batch", None, "ssm_heads", None)
        ch = shard_constraint(jnp.repeat(cq, rep, axis=2),
                              "batch", None, "ssm_heads", None)
        cum = jnp.cumsum(dtq * A, axis=1)                    # (B,Q,H)
        # intra-chunk quadratic term; above the diagonal diff is positive
        # and exp would overflow, so mask before exp: exp(-inf) = 0 there,
        # with a zero gradient
        diff = cum[:, :, None, :] - cum[:, None, :, :]       # (B,Q,Q,H)
        decay = jnp.exp(jnp.where(tri[None, :, :, None], diff, -jnp.inf))
        decay = shard_constraint(decay, "batch", None, None, "ssm_heads")
        cb = jnp.einsum("bqhs,bkhs->bqkh", ch, bh)
        scores = cb * decay * dtq[:, None, :, :]             # (B,Q,K,H)
        y = jnp.einsum("bqkh,bkhp->bqhp", scores, xq)
        # inter-chunk contribution from the carried state
        y = y + jnp.einsum("bqh,bqhs,bhps->bqhp", jnp.exp(cum), ch, h_prev)
        # state update
        tail = jnp.exp(cum[:, -1:, :] - cum)                 # (B,Q,H)
        sstate = jnp.einsum("bqh,bqhs,bqhp->bhps", tail * dtq, bh, xq)
        h = h_prev * jnp.exp(cum[:, -1, :])[..., None, None] + sstate
        return h, y

    body = jax.checkpoint(body)
    h0 = jnp.zeros((B, H, P, N), f32)
    _, ys = jax.lax.scan(body, h0, (xc, dtc, bc, cc),
                         unroll=nc if _SCAN["unroll"] else 1)
    y = ys.swapaxes(0, 1).reshape(B, L, H, P)
    return y.astype(x.dtype)


def ssd_final_state(x, dt, a_log, b, *, chunk: int):
    """Final h state after processing the sequence (for prefill -> decode)."""
    B, L, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    A = -jnp.exp(a_log.astype(jnp.float32))
    dt = dt.astype(jnp.float32)
    dA = (dt * A)
    cum = jnp.cumsum(dA, axis=1)                             # (B,L,H)
    tail = jnp.exp(cum[:, -1:, :] - cum)                     # (B,L,H)
    bh = jnp.repeat(b, rep, axis=2).astype(jnp.float32)
    h = jnp.einsum("blh,blhn,blhp->bhpn", tail * dt, bh, x.astype(jnp.float32))
    return h                                                  # (B,H,P,N)


def ssm_forward(cfg: ModelConfig, p: dict, xin, *, mode: str,
                cache: Optional[dict] = None) -> Tuple[jax.Array, Optional[dict]]:
    """Full Mamba-2 block. xin: (B,L,D). Returns (y, new_cache)."""
    ss, d_inner, nh, gn, conv_dim = _dims(cfg)
    B, L, D = xin.shape
    zxbcdt = jnp.einsum("bld,de->ble", xin, p["in_proj"])
    zxbcdt = shard_constraint(zxbcdt, "batch", None, "ssm_inner")
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + conv_dim]
    dt_raw = zxbcdt[..., d_inner + conv_dim:]

    if mode == "decode":
        assert cache is not None and L == 1
        window = jnp.concatenate([cache["conv"], xbc], axis=1)  # (B,W,conv)
        new_conv = window[:, 1:, :]
        w = p["conv_w"]
        xbc_c = jnp.einsum("bwc,wc->bc", window, w)[:, None, :] + p["conv_b"]
    else:
        new_conv = None
        with jax.named_scope("conv"):
            xbc_c = _causal_conv(xbc, p["conv_w"], p["conv_b"])
        if mode == "prefill":
            pad = jnp.pad(xbc, ((0, 0), (ss.conv_width - 1, 0), (0, 0)))
            new_conv = pad[:, L:L + ss.conv_width - 1, :]  # last W-1 inputs
    xbc_c = jax.nn.silu(xbc_c.astype(jnp.float32)).astype(xin.dtype)

    xs = xbc_c[..., :d_inner].reshape(B, L, nh, ss.head_dim)
    b = xbc_c[..., d_inner:d_inner + gn].reshape(B, L, ss.num_groups, ss.d_state)
    c = xbc_c[..., d_inner + gn:].reshape(B, L, ss.num_groups, ss.d_state)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) +
                         p["dt_bias"].astype(jnp.float32))    # (B,L,H)

    if mode == "decode":
        A = -jnp.exp(p["a_log"].astype(jnp.float32))
        a_t = jnp.exp(dt[:, 0] * A)                           # (B,H)
        rep = nh // ss.num_groups
        bh = jnp.repeat(b[:, 0], rep, axis=1).astype(jnp.float32)  # (B,H,N)
        ch = jnp.repeat(c[:, 0], rep, axis=1).astype(jnp.float32)
        xf = xs[:, 0].astype(jnp.float32)                     # (B,H,P)
        h = cache["h"] * a_t[..., None, None] + \
            (dt[:, 0, :, None] * xf)[..., None] * bh[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", h, ch)[:, None]       # (B,1,H,P)
        new_cache = {"conv": new_conv, "h": h}
    else:
        from repro.kernels import ops as kops
        with jax.named_scope("ssd"):
            if kops.pallas_enabled():
                y = kops.ssd(xs, dt, p["a_log"], b, c, chunk=ss.chunk)
            else:
                y = ssd_chunked(xs, dt, p["a_log"], b, c, chunk=ss.chunk)
        if mode == "prefill":
            h = ssd_final_state(xs, dt, p["a_log"], b, chunk=ss.chunk)
            new_cache = {"conv": new_conv, "h": h}
        else:
            new_cache = None

    y = y + p["d_skip"].astype(jnp.float32)[None, None, :, None].astype(y.dtype) \
        * xs.astype(y.dtype)
    y = y.reshape(B, L, d_inner).astype(xin.dtype)
    y = gated_rmsnorm(y, z, p["out_norm"], cfg.norm_eps)
    y = shard_constraint(y, "batch", None, "ssm_inner")
    return jnp.einsum("ble,ed->bld", y, p["out_proj"]), new_cache
