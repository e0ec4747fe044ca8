"""Kernel smoke subset for the GATING fast lane: float32 cases at the
smallest shapes, interpret mode.  The full dtype/shape sweep stays in
tests/test_kernels.py under the `slow` marker (non-blocking CI lane); this
file exists so a Pallas API drift breaks the build immediately instead of
silently reddening the slow lane."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_fwd

KEY = jax.random.PRNGKey(0)


def _gqa_qkv(hd=128):
    # GQA at the kernels' smallest lane-aligned shape: 2 blocks of 128 rows
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 256, 4, hd), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, hd), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, hd), jnp.float32)
    return q, k, v


def test_flash_attention_smoke():
    q, k, v = _gqa_qkv()
    out = ops.flash_attention(q, k, v, block_q=128, block_k=128,
                              interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_lse_smoke():
    q, k, v = _gqa_qkv()
    _, lse = flash_attention_fwd(q, k, v, block_q=128, block_k=128,
                                 interpret=True)
    B, S, H, hd = q.shape
    s = jnp.einsum("bskgh,btkh->bkgst", q.reshape(B, S, 2, 2, hd), k)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s * hd ** -0.5, -jnp.inf)
    exp = jax.nn.logsumexp(s, axis=-1).reshape(B, H, 1, S)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(exp), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_hd64_smoke():
    # heads of one 64-row tile (hd on sublanes), as granite-4.0-h's
    q, k, v = _gqa_qkv(hd=64)
    out = ops.flash_attention(q, k, v, scale=0.015625, block_q=128,
                              block_k=128, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True, scale=0.015625)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5,
                               rtol=2e-5)


def _grad_matches_ref(hd, scale=None):
    q, k, v = _gqa_qkv(hd)
    w = jax.random.normal(jax.random.PRNGKey(1), q.shape, jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(ops.flash_attention(
        *a, scale=scale, block_q=128, block_k=128, interpret=True) * w),
        (0, 1, 2))(q, k, v)
    exp = jax.grad(lambda *a: jnp.sum(ref.flash_attention_ref(
        *a, causal=True, scale=scale) * w), (0, 1, 2))(q, k, v)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), atol=2e-5,
                                   rtol=2e-5)


def test_flash_attention_grad_smoke():
    _grad_matches_ref(128)


def test_flash_attention_hd64_grad_smoke():
    _grad_matches_ref(64, scale=0.015625)


def test_ssd_smoke():
    ks = jax.random.split(KEY, 5)
    B, L, H, P, G, N = 1, 32, 2, 8, 1, 8
    x = jax.random.normal(ks[0], (B, L, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    a_log = jnp.log(jax.random.uniform(ks[2], (H,), minval=1.0, maxval=8.0))
    b = jax.random.normal(ks[3], (B, L, G, N)) * 0.3
    c = jax.random.normal(ks[4], (B, L, G, N)) * 0.3
    out = ops.ssd(x, dt, a_log, b, c, chunk=16, interpret=True)
    exp = ref.ssd_ref(x, dt, a_log, b, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-4,
                               rtol=1e-4)


# interpret=None: CPU arrays select interpret mode by themselves
@pytest.mark.parametrize("interpret", [True, None])
def test_rmsnorm_smoke(interpret):
    x = jax.random.normal(KEY, (7, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64,), jnp.float32)
    out = ops.rmsnorm(x, w, interpret=interpret)
    exp = ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-6,
                               rtol=1e-6)

