"""Pure-jnp oracles for every Pallas kernel (the ground truth in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True, scale: float = None):
    """q: (B,S,H,hd); k,v: (B,S,KV,hd). Naive fp32 attention with GQA."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = hd ** -0.5 if scale is None else scale
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32) * scale
    if causal:
        Sk = k.shape[1]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        scores = jnp.where(mask[None, None, None], scores,
                           jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, Sq, H, hd)


def ssd_ref(x, dt, a_log, b, c):
    """Naive O(L) SSD recurrence (fp32 state), the slow-but-exact oracle.

    x: (B,L,H,P); dt: (B,L,H) post-softplus; a_log: (H,); b,c: (B,L,G,N).
    h_t = exp(A*dt_t) h_{t-1} + dt_t * (B_t (x) x_t);  y_t = h_t C_t
    """
    B, L, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    rep = H // G
    A = -jnp.exp(a_log.astype(jnp.float32))
    bh = jnp.repeat(b, rep, axis=2).astype(jnp.float32)   # (B,L,H,N)
    ch = jnp.repeat(c, rep, axis=2).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)

    def step(h, inp):
        xt, dtt, bt, ct = inp            # (B,H,P), (B,H), (B,H,N), (B,H,N)
        a_t = jnp.exp(dtt * A)           # (B,H)
        h = h * a_t[..., None, None] + \
            (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", h, ct)
        return h, y

    h0 = jnp.zeros((B, H, P, N), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (xf.swapaxes(0, 1), dtf.swapaxes(0, 1),
                                    bh.swapaxes(0, 1), ch.swapaxes(0, 1)))
    return ys.swapaxes(0, 1).astype(x.dtype)             # (B,L,H,P)
