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

KEY = jax.random.PRNGKey(0)


def test_flash_attention_smoke():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 128, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 128, 2, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5,
                               rtol=2e-5)


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

