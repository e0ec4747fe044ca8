"""Which attention path the model takes.  Causal self-attention in train or
prefill, at shapes the flash kernels' blocks tile (head sizes a multiple of
64, lengths of 128) and on one device, goes through
``kernels.ops.causal_attention``, which takes the Pallas op only where the
call is lowered for a TPU; every other call keeps its path.  MLA decode
scores against its latent cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.configs import smoke_config
from repro.kernels.blocked import blocked_attention
from repro.models import attention as A
from repro.models.params import init_params
from repro.sharding import AxisRules, axis_rules


def _qkv(S=256, Sk=None, hd=128, hdv=None):
    Sk = S if Sk is None else Sk
    return (jnp.zeros((1, S, 4, hd)), jnp.zeros((1, Sk, 2, hd)),
            jnp.zeros((1, Sk, 2, hdv or hd)))


@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("mode", ["train", "prefill"])
def test_causal_self_attention_may_take_flash(mode, hd):
    assert A._use_flash(mode, *_qkv(hd=hd), causal=True, kv_override=None)


@pytest.mark.parametrize("kernel", ["fwd", "dkv", "dq"])
def test_hd128_keeps_its_blocks(kernel):
    from repro.kernels import flash_attention as fa
    assert fa.blocks(128, kernel) == (512, 512)


def test_one_device_mesh_may_take_flash():
    rules = AxisRules(mesh=AbstractMesh((1,), ("data",)))
    with axis_rules(rules):
        assert A._use_flash("train", *_qkv(), causal=True, kv_override=None)


@pytest.mark.parametrize("case", [
    "decode", "cross_attention", "non_causal", "hd_ne_hdv", "hd_unaligned",
    "hd_below_a_tile", "seq_unaligned", "q_shorter_than_k", "two_devices"])
def test_other_calls_keep_the_blocked_path(case):
    mode, causal, kv_override, qkv = "train", True, None, _qkv()
    if case == "decode":
        mode = "decode"
    elif case == "cross_attention":
        kv_override = qkv[1:]
    elif case == "non_causal":
        causal = False
    elif case == "hd_ne_hdv":          # MLA: q/k heads 192, v heads 128
        qkv = _qkv(hd=256, hdv=128)
    elif case == "hd_unaligned":
        qkv = _qkv(hd=96)
    elif case == "hd_below_a_tile":
        qkv = _qkv(hd=32)
    elif case == "seq_unaligned":
        qkv = _qkv(S=200)
    elif case == "q_shorter_than_k":
        qkv = _qkv(S=128, Sk=256)
    rules = (AxisRules(mesh=AbstractMesh((2,), ("data",)))
             if case == "two_devices" else None)
    with axis_rules(rules):
        assert not A._use_flash(mode, *qkv, causal=causal,
                                kv_override=kv_override)


def test_cpu_train_step_lowers_to_the_blocked_scan():
    cfg = smoke_config("yi-6b").with_(head_dim=128, dtype="float32")
    p = init_params(cfg, jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a[0], p["decoder"]["blocks"]["sub0"]["mixer"])
    S = 256
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, cfg.d_model))
    pos = jnp.arange(S)
    q, k, v = _qkv(S)
    assert A._use_flash("train", q, k, v, causal=True, kv_override=None)

    def loss(p, x):
        y, _ = A.attn_forward(cfg, p, x, positions=pos, mode="train")
        return jnp.sum(y ** 2)

    text = jax.jit(jax.grad(loss)).lower(p, x).as_text()
    assert "tpu_custom_call" not in text and "while" in text

    # the same numbers as the blocked scan called directly
    def blocked(p, x):
        from repro.models.layers import apply_rope
        hd = cfg.resolved_head_dim
        qq = apply_rope(jnp.einsum("bsd,dhk->bshk", x, p["wq"]), pos,
                        cfg.rope_theta)
        kk = apply_rope(jnp.einsum("bsd,dhk->bshk", x, p["wk"]), pos,
                        cfg.rope_theta)
        vv = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        o = blocked_attention(qq, kk, vv, True, hd ** -0.5)
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"])

    got, _ = jax.jit(lambda p, x: A.attn_forward(
        cfg, p, x, positions=pos, mode="train"))(p, x)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jax.jit(blocked)(p, x)))


def test_mla_decode_matches_expanded_keys():
    # decode folds W_UK into the query and W_UV into the output, scoring
    # against the latent cache; the oracle expands per-head keys and values
    # from that cache, at deepseek-v2's smoke widths
    from repro.models.layers import apply_rope, rmsnorm
    cfg = smoke_config("deepseek-v2-236b").with_(dtype="float32")
    a, eps = cfg.mla, cfg.norm_eps
    p = init_params(cfg, jax.random.PRNGKey(0))
    p = p["decoder"]["prefix"]["layer0"]["mixer"]
    B, T, pos = 2, 16, 5
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], (B, 1, cfg.d_model))
    cache = {"ckv": jax.random.normal(ks[1], (B, T, a.kv_lora_rank)),
             "krope": jax.random.normal(ks[2], (B, T, a.qk_rope_head_dim))}
    positions = pos + jnp.arange(1)
    y, new_cache = A.mla_forward(cfg, p, x, positions=positions,
                                 mode="decode", cache=cache,
                                 pos=jnp.int32(pos))

    nope = a.qk_nope_head_dim
    q = jnp.einsum("bsl,lhk->bshk", rmsnorm(
        jnp.einsum("bsd,dl->bsl", x, p["wq_a"]), p["q_norm"], eps),
        p["wq_b"])
    q_nope = q[..., :nope]
    q_rope = apply_rope(q[..., nope:], positions, cfg.rope_theta)
    ckv_kr = jnp.einsum("bsd,dl->bsl", x, p["wkv_a"])
    ckv = rmsnorm(ckv_kr[..., :a.kv_lora_rank], p["kv_norm"], eps)
    krope = apply_rope(ckv_kr[:, :, None, a.kv_lora_rank:], positions,
                       cfg.rope_theta)[:, :, 0]
    ckv_all = cache["ckv"].at[:, pos].set(ckv[:, 0])
    krope_all = cache["krope"].at[:, pos].set(krope[:, 0])
    k_nope = jnp.einsum("btl,lhn->bthn", ckv_all, p["wkv_b"][..., :nope])
    v = jnp.einsum("btl,lhv->bthv", ckv_all, p["wkv_b"][..., nope:])
    scores = (jnp.einsum("bshn,bthn->bhst", q_nope, k_nope) +
              jnp.einsum("bshr,btr->bhst", q_rope, krope_all))
    scores = scores * (nope + a.qk_rope_head_dim) ** -0.5
    scores = jnp.where(jnp.arange(T) <= pos, scores, -jnp.inf)
    out = jnp.einsum("bhst,bthv->bshv", jax.nn.softmax(scores, axis=-1), v)
    want = jnp.einsum("bshv,hvd->bsd", out, p["wo"])

    np.testing.assert_array_equal(np.asarray(new_cache["ckv"]),
                                  np.asarray(ckv_all))
    np.testing.assert_array_equal(np.asarray(new_cache["krope"]),
                                  np.asarray(krope_all))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5,
                               rtol=1e-5)
