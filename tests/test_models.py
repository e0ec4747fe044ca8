"""Per-arch smoke tests (reduced configs, one fwd/train step on CPU) and
model-level correctness: decode == teacher forcing, MoE gather == dense."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# ~3 min of per-arch jit compiles: slow lane (CI runs it non-blocking)
pytestmark = pytest.mark.slow

from repro.configs import ALL_ARCHS, smoke_config
from repro.models import (decode_step, forward_hidden, init_params, loss_fn,
                          pad_cache, prefill)
from repro.models.model import _head_input, _head_weight
from repro.models.moe import moe_dense, moe_forward

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=32):
    b = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size),
         "labels": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                      cfg.vocab_size)}
    if cfg.enc_layers:
        b["enc_embeds"] = jax.random.normal(KEY, (B, S, cfg.d_model),
                                            jnp.float32)
    return b


@pytest.mark.parametrize("arch", sorted(ALL_ARCHS))
def test_smoke_forward_and_train_step(arch):
    """Assignment requirement: reduced config, one forward/train step on CPU,
    output shapes + no NaNs."""
    cfg = smoke_config(arch).with_(dtype="float32")
    params = init_params(cfg, KEY)
    batch = _batch(cfg)
    hidden, aux = forward_hidden(cfg, params, batch)
    assert hidden.shape == (2, 32, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(hidden)))
    loss, metrics = jax.jit(lambda p, b: loss_fn(cfg, p, b))(params, batch)
    assert np.isfinite(float(loss))
    grads = jax.grad(lambda p: loss_fn(cfg, p, batch)[0])(params)
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(leaf)))


@pytest.mark.parametrize("arch", ["yi-6b", "mamba2-1.3b", "jamba-v0.1-52b",
                                  "deepseek-v2-236b", "chameleon-34b",
                                  "seamless-m4t-large-v2",
                                  "granite-4.0-h-micro"])
def test_decode_matches_teacher_forcing(arch):
    """prefill + step-by-step decode reproduces the full-forward logits
    (MoE archs at a capacity that drops no token — capacity dispatch is
    batch-dependent)."""
    cfg = smoke_config(arch).with_(dtype="float32")
    if cfg.moe is not None:
        cfg = cfg.with_(moe=dataclasses.replace(cfg.moe,
                                                capacity_factor=100.0))
    params = init_params(cfg, jax.random.PRNGKey(42))
    B, S, S0 = 2, 16, 8
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0,
                              cfg.vocab_size)
    fb = {"tokens": toks, "labels": toks}
    if cfg.enc_layers:
        fb["enc_embeds"] = jax.random.normal(KEY, (B, 8, cfg.d_model))
    hid, _ = forward_hidden(cfg, params, fb, mode="train")
    full = jnp.einsum("bsd,dv->bsv", _head_input(cfg, hid),
                      _head_weight(cfg, params))[..., :cfg.vocab_size]
    pb = {"tokens": toks[:, :S0]}
    if cfg.enc_layers:
        pb["enc_embeds"] = fb["enc_embeds"]
    cache, logits = prefill(cfg, params, pb)
    cache = pad_cache(cfg, cache, S0, S)
    errs = [float(jnp.max(jnp.abs(logits - full[:, S0 - 1])))]
    for t in range(S0, S):
        logits, cache = decode_step(cfg, params, cache, toks[:, t:t + 1],
                                    jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(logits - full[:, t]))))
    assert max(errs) < 2e-4, errs


def test_moe_gather_matches_dense_at_high_capacity():
    cfg = smoke_config("granite-moe-3b-a800m").with_(dtype="float32")
    cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, capacity_factor=100.0))
    params = init_params(cfg, KEY)
    p = jax.tree.map(lambda a: a[0], params["decoder"]["blocks"])["sub0"]["ff"]
    x = jax.random.normal(KEY, (2, 16, cfg.d_model)) * 0.3
    yd, auxd = moe_dense(cfg, p, x)
    yg, auxg = moe_forward(cfg, p, x)
    np.testing.assert_allclose(np.asarray(yd), np.asarray(yg), atol=1e-5,
                               rtol=1e-5)
    assert float(abs(auxd - auxg)) < 1e-6


def test_moe_capacity_drops_tokens():
    """At low capacity the gather path drops overflow tokens (GShard-style);
    output differs from dense but stays finite."""
    cfg = smoke_config("granite-moe-3b-a800m").with_(dtype="float32")
    cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, capacity_factor=0.25))
    params = init_params(cfg, KEY)
    p = jax.tree.map(lambda a: a[0], params["decoder"]["blocks"])["sub0"]["ff"]
    x = jax.random.normal(KEY, (2, 64, cfg.d_model)) * 0.3
    y, aux = moe_forward(cfg, p, x)
    assert bool(jnp.all(jnp.isfinite(y)))


def test_moe_router_aux_loss_balanced_vs_collapsed():
    from repro.models.moe import load_balance_loss
    E = 8
    probs_bal = jnp.full((4, 16, E), 1.0 / E)
    ids_bal = jnp.tile(jnp.arange(E)[None, None, :2], (4, 16, 1)) + \
        (jnp.arange(16) % E)[None, :, None]
    ids_bal = ids_bal % E
    probs_col = jnp.zeros((4, 16, E)).at[..., 0].set(1.0)
    ids_col = jnp.zeros((4, 16, 2), jnp.int32)
    bal = load_balance_loss(probs_bal, ids_bal, E)
    col = load_balance_loss(probs_col, ids_col, E)
    assert float(col) > float(bal)
    assert float(bal) == pytest.approx(1.0, rel=1e-5)


def test_rope_relative_position_property():
    """RoPE inner products depend only on relative distance."""
    from repro.models.layers import apply_rope
    hd = 32
    q = jax.random.normal(KEY, (1, 1, 1, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, hd))
    def score(pq, pk):
        qr = apply_rope(q, jnp.array([pq]), 10_000.0)
        kr = apply_rope(k, jnp.array([pk]), 10_000.0)
        return float(jnp.sum(qr * kr))
    assert score(5, 3) == pytest.approx(score(105, 103), abs=1e-4)
    assert score(5, 3) != pytest.approx(score(5, 4), abs=1e-4)


def test_loss_decreases_on_learnable_stream():
    """Few steps of AdamW on the synthetic stream reduce loss."""
    from repro.core.elastic import ElasticTrainer, TrainJobConfig
    cfg = smoke_config("yi-6b")
    tr = ElasticTrainer(cfg, TrainJobConfig(global_batch=4, seq_len=32,
                                            total_steps=15, seed=0),
                        jax.devices()[:1])
    first = tr.step()["loss"]
    for _ in range(14):
        last = tr.step()["loss"]
    assert last < first


def test_vocab_padding_masked_in_loss():
    """Padded vocab columns must not affect the loss."""
    cfg = smoke_config("yi-6b").with_(dtype="float32", vocab_pad_to=1)
    cfg_pad = cfg.with_(vocab_pad_to=96)
    assert cfg_pad.padded_vocab > cfg.vocab_size
    params = init_params(cfg, KEY)
    params_pad = init_params(cfg_pad, KEY)
    # overwrite the padded model's valid rows with the unpadded weights
    params_pad["embed"] = params_pad["embed"].at[:cfg.vocab_size].set(
        params["embed"])
    params_pad["lm_head"] = params_pad["lm_head"].at[:, :cfg.vocab_size].set(
        params["lm_head"])
    for k_ in ("decoder", "final_norm"):
        params_pad[k_] = params[k_]
    batch = _batch(cfg)
    l1, _ = loss_fn(cfg, params, batch)
    l2, _ = loss_fn(cfg_pad, params_pad, batch)
    assert float(l1) == pytest.approx(float(l2), rel=1e-5)
