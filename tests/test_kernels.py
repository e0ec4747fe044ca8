"""Pallas kernels (interpret mode) and the blocked-jnp twin vs. ref oracles:
shape/dtype sweeps per the assignment."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# interpret-mode sweeps take minutes: slow lane (CI runs it non-blocking;
# the 22 failing cases are known seed debt — see ROADMAP "Open items")
pytestmark = pytest.mark.slow

from repro.kernels import ops, ref
from repro.kernels.blocked import blocked_attention

KEY = jax.random.PRNGKey(0)


def _qkv(B, S, H, KV, hd, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    return q, k, v


# the kernels take heads of whole 64-row tiles and lane-aligned lengths
# (head_dim a multiple of 64, S of 128); other shapes keep the blocked path
@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 4, 4, 128),    # MHA
    (2, 256, 4, 2, 128),    # GQA
    (1, 512, 8, 2, 128),    # long-ish, high group ratio
    (2, 128, 6, 3, 128),    # non-pow2 heads
    (2, 256, 8, 2, 64),     # GQA at hd 64
    (1, 512, 4, 1, 64),     # hd 64, one KV head
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(B, S, H, KV, hd, dtype):
    q, k, v = _qkv(B, S, H, KV, hd, dtype)
    out = ops.flash_attention(q, k, v, interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (256, 128),
                                             (128, 256)])
def test_flash_attention_block_shapes(block_q, block_k):
    q, k, v = _qkv(2, 512, 4, 2, 128, jnp.float32)
    out = ops.flash_attention(q, k, v, block_q=block_q, block_k=block_k,
                              interpret=True)
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_hd64_default_blocks_fwd_and_grads():
    # hd 64's preferred blocks run as chosen: S holds two of the largest
    from repro.kernels import flash_attention as fa
    S = 2 * max(max(fa.blocks(64, k)) for k in ("fwd", "dkv", "dq"))
    q, k, v = _qkv(1, S, 2, 1, 64, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), q.shape)
    out = ops.flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(ref.flash_attention_ref(q, k, v, causal=True)),
        atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(ops.flash_attention(
        *a, interpret=True) * w), (0, 1, 2))(q, k, v)
    exp = jax.grad(lambda *a: jnp.sum(
        ref.flash_attention_ref(*a, causal=True) * w), (0, 1, 2))(q, k, v)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e), atol=1e-4,
                                   rtol=1e-4)


def test_flash_attention_grad_flows():
    q, k, v = _qkv(1, 128, 4, 2, 128, jnp.float32)
    g = jax.grad(lambda q_: jnp.sum(
        ops.flash_attention(q_, k, v, interpret=True) ** 2))(q)
    gr = jax.grad(lambda q_: jnp.sum(
        ref.flash_attention_ref(q_, k, v, causal=True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("B,L,H,P,G,N,chunk", [
    (1, 64, 4, 16, 1, 16, 16),
    (2, 64, 4, 16, 2, 16, 16),
    (1, 128, 8, 32, 1, 32, 32),
    (2, 96, 6, 16, 3, 8, 32),      # non-pow2, chunk > some dims
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_vs_naive_recurrence(B, L, H, P, G, N, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    x = (jax.random.normal(ks[0], (B, L, H, P)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    a_log = jnp.log(jax.random.uniform(ks[2], (H,), minval=1.0, maxval=8.0))
    b = (jax.random.normal(ks[3], (B, L, G, N)) * 0.3).astype(dtype)
    c = (jax.random.normal(ks[4], (B, L, G, N)) * 0.3).astype(dtype)
    exp = ref.ssd_ref(x, dt, a_log, b, c)
    out = ops.ssd(x, dt, a_log, b, c, chunk=chunk, interpret=True)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


def test_ssd_jnp_chunked_matches_kernel_math():
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(KEY, 5)
    B, L, H, P, G, N = 2, 64, 4, 16, 2, 16
    x = jax.random.normal(ks[0], (B, L, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    a_log = jnp.log(jax.random.uniform(ks[2], (H,), minval=1.0, maxval=8.0))
    b = jax.random.normal(ks[3], (B, L, G, N)) * 0.3
    c = jax.random.normal(ks[4], (B, L, G, N)) * 0.3
    got = ssd_chunked(x, dt, a_log, b, c, chunk=16)
    exp = ops.ssd(x, dt, a_log, b, c, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=1e-5,
                               rtol=1e-5)


def test_ssd_grad_matches_chunked_jnp():
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(KEY, 5)
    B, L, H, P, G, N = 1, 32, 2, 8, 1, 8
    x = jax.random.normal(ks[0], (B, L, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    a_log = jnp.log(jax.random.uniform(ks[2], (H,), minval=1.0, maxval=4.0))
    b = jax.random.normal(ks[3], (B, L, G, N)) * 0.3
    c = jax.random.normal(ks[4], (B, L, G, N)) * 0.3
    g1 = jax.grad(lambda x_: jnp.sum(
        ops.ssd(x_, dt, a_log, b, c, chunk=8, interpret=True) ** 2))(x)
    g2 = jax.grad(lambda x_: jnp.sum(
        ssd_chunked(x_, dt, a_log, b, c, chunk=8) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("shape", [(7, 64), (8, 33, 128), (2, 3, 4, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), shape[-1:], jnp.float32)
    out = ops.rmsnorm(x, w, interpret=True)
    exp = ref.rmsnorm_ref(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# blocked (XLA) flash twin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,hd,bk", [
    (2, 128, 4, 2, 32, 32),
    (1, 100, 6, 2, 16, 48),    # Sk not a multiple of block
    (2, 64, 4, 4, 32, 64),
])
def test_blocked_attention_fwd_and_grads(B, S, H, KV, hd, bk):
    q, k, v = _qkv(B, S, H, KV, hd, jnp.float32)
    out = blocked_attention(q, k, v, True, None, 0, None, bk)
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5,
                               rtol=2e-5)
    gb = jax.grad(lambda *a: jnp.sum(
        blocked_attention(*a, True, None, 0, None, bk) ** 2), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(
        ref.flash_attention_ref(*a, causal=True) ** 2), (0, 1, 2))(q, k, v)
    for a, b in zip(gb, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=5e-4)


# ---------------------------------------------------------------------------
# fused checkpoint pack (fast-lane gather/pack)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shapes,dtype", [
    ([(128,), (8, 128), (1000,)], jnp.float32),       # uneven lane padding
    ([(256, 256), (1,), (3, 5, 7)], jnp.float32),     # big + scalarish + odd
    ([(64, 64), (4096,)], jnp.bfloat16),              # sub-word dtype
    ([(17,), (129,), (130, 2)], jnp.int32),           # all off-lane
])
def test_pack_kernel_sweep_vs_ref(shapes, dtype):
    from repro.kernels.pack import pack_leaves_pallas, pack_leaves_ref
    ks = jax.random.split(KEY, len(shapes))
    if jnp.issubdtype(dtype, jnp.integer):
        leaves = [jax.random.randint(k, s, -100, 100, dtype)
                  for k, s in zip(ks, shapes)]
    else:
        leaves = [jax.random.normal(k, s, dtype) for k, s in zip(ks, shapes)]
    out = pack_leaves_pallas(leaves, interpret=True)
    exp = pack_leaves_ref(leaves)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


@pytest.mark.parametrize("block_rows", [4, 8, 16])
def test_pack_kernel_block_rows(block_rows):
    from repro.kernels.pack import pack_leaves_pallas, pack_leaves_ref
    leaves = [jax.random.normal(k, (n,))
              for k, n in zip(jax.random.split(KEY, 3), (700, 129, 2048))]
    out = pack_leaves_pallas(leaves, block_rows=block_rows, interpret=True)
    exp = pack_leaves_ref(leaves, block_rows=block_rows)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_blocked_attention_non_causal_and_hdv():
    """Cross-attention form: no mask, v head dim differs from qk head dim."""
    B, Sq, Sk, H, hd, hdv = 2, 32, 48, 4, 16, 24
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd))
    k = jax.random.normal(ks[1], (B, Sk, H, hd))
    v = jax.random.normal(ks[2], (B, Sk, H, hdv))
    out = blocked_attention(q, k, v, False, None, 0, None, 16)
    # naive reference with distinct v dim
    s = jnp.einsum("bshd,bthd->bhst", q, k) * hd ** -0.5
    p = jax.nn.softmax(s, -1)
    exp = jnp.einsum("bhst,bthv->bshv", p, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5,
                               rtol=2e-5)
