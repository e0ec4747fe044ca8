"""granite-4.0-h-micro against its plain reference (``bench/models``), and
the dense path against its own, on the CPU at small sizes.

The program's loss and gradients through ``loss_fn`` are compared with the
reference's on seeded weights made by the benchmark's own rule, installed
by path exactly as the benchmark installs them.
"""
import copy
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import driver, program, spec, weights
from bench.models import dense_gqa, granite_hybrid, mamba2
from repro.core.elastic import ElasticTrainer, TrainJobConfig
from repro.models import (abstract_params, forward_hidden, init_params,
                          loss_fn)
from repro.models.ssm import ssd_chunked

SEED = 2**33 + 5
B, S = 2, 32

# every Granite scalar away from 1, so that each is checked; one period of
# an SSM layer and an attention layer (stacked under decoder/blocks)
GRANITE = {
    "program_arch": "granite-4.0-h-micro", "dtype": "float32",
    "num_layers": 2, "d_model": 64, "num_heads": 2, "num_kv_heads": 1,
    "head_dim": 32, "d_ff": 128, "vocab_size": 256,
    "attn_every": 2, "attn_offset": 1,
    "ssm": {"d_state": 16, "head_dim": 16, "expand": 1, "num_groups": 1,
            "conv_width": 4, "chunk": 8},
    "norm_eps": 1e-5, "tie_embeddings": True,
    "position_embedding_type": "nope", "attention_multiplier": 0.015625,
    "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
    "logits_scaling": 8.0,
}
DENSE = {
    "program_arch": "yi-6b", "dtype": "float32",
    "num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
    "head_dim": 16, "d_ff": 128, "vocab_size": 128, "rope_theta": 5e6,
    "norm_eps": 1e-5, "tie_embeddings": False,
}
# f32 on the CPU, where a matmul is exact to f32 rounding: the two sides
# order their sums differently (the chunked SSD scan against the quadratic
# form, the blocked softmax against the plain one, the chunked loss against
# the whole), so they part by a few ulps of the largest terms, ~1e-6
# relative.  A mistake in any of Granite's equations (a multiplier left
# out, rotary left on, the default softmax scale) moves the worst leaf's
# gradient by more than that leaf's largest magnitude.
LOSS_RTOL = 1e-5
GRAD_TOL = 1e-4        # of the leaf's largest reference magnitude


def _batch(vocab: int):
    rng = np.random.default_rng(0)
    toks = rng.integers(0, vocab, (B, S + 1)).astype(np.int32)
    return {"tokens": jnp.asarray(toks[:, :-1]),
            "labels": jnp.asarray(toks[:, 1:])}


def _program_params(cfg, model, conf):
    """The program's parameter tree holding the benchmark's seeded weights."""
    leaves, paths, treedef = program.flat(init_params(cfg, jax.random.key(0)))
    shapes = model.param_shapes(conf)
    assert {p: tuple(x.shape) for p, x in leaves.items()} == shapes
    new = weights.make(shapes, model.init_rule, SEED)
    return jax.tree_util.tree_unflatten(treedef, [new[p] for p in paths]), new


def _compare(conf, model):
    cfg = program.model_config(conf)
    params, flat = _program_params(cfg, model, conf)
    batch = _batch(conf["vocab_size"])
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(cfg, p, batch)[0]))(params)
    grads, _, _ = program.flat(grads)

    def ref_mean(p):
        rows = [model.row_loss_sum(conf, p, batch["tokens"][r],
                                   batch["labels"][r]) for r in range(B)]
        return sum(rows) / (B * S)

    ref_loss, ref_grads = jax.jit(jax.value_and_grad(ref_mean))(flat)
    assert abs(float(loss) - float(ref_loss)) <= LOSS_RTOL * abs(
        float(ref_loss)), (float(loss), float(ref_loss))
    for k, g in ref_grads.items():
        err = float(jnp.max(jnp.abs(grads[k] - g)))
        assert err <= GRAD_TOL * float(jnp.max(jnp.abs(g))), (k, err)


def test_granite_loss_and_gradients_match_the_reference():
    _compare(GRANITE, granite_hybrid)


def test_dense_loss_and_gradients_match_the_reference_at_default_scalars():
    # yi-6b's path with the Granite fields at their defaults: the guard on
    # the code both configurations share
    cfg = program.model_config(DENSE)
    assert (cfg.position_embedding_type, cfg.attention_multiplier,
            cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == ("rope", None, 1.0, 1.0, 1.0)
    _compare(DENSE, dense_gqa)


def test_ssd_gradient_is_finite_where_the_decay_overflows():
    # A = -16 and dt = 1: within a chunk of 8 the exponent above the
    # diagonal reaches 16 * 7 = 112, past float32's exp limit of ~88.7
    L, H, P, N, Q = 16, 2, 4, 8, 8
    ks = jax.random.split(jax.random.key(3), 4)
    x = jax.random.normal(ks[0], (1, L, H, P))
    b = jax.random.normal(ks[1], (1, L, 1, N))
    c = jax.random.normal(ks[2], (1, L, 1, N))
    w = jax.random.normal(ks[3], (1, L, H, P))
    dt = jnp.ones((1, L, H))
    a_log = jnp.full((H,), np.log(16.0), jnp.float32)
    assert 16 * (Q - 1) > np.log(np.finfo(np.float32).max)

    def prog(x, dt, a_log, b, c):
        return jnp.sum(ssd_chunked(x, dt, a_log, b, c, chunk=Q) * w)

    def ref(x, dt, a_log, b, c):
        y = mamba2._ssd(x[0], dt[0], -jnp.exp(a_log), b[0], c[0])
        return jnp.sum(y * w[0])

    args = (x, dt, a_log, b, c)
    got = jax.grad(prog, argnums=range(5))(*args)
    want = jax.grad(ref, argnums=range(5))(*args)
    # one scale for all five: at A = -16 the a_log gradient (~1e-4) is the
    # remainder of diagonal terms of ~10 that cancel, and both sides round
    # it at 1e-5 of the largest gradient (dt's, ~16); float64 differs from
    # each by as much
    scale = max(float(jnp.max(jnp.abs(r))) for r in want)
    for g, r in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-5 * scale)


def test_compiled_granite_step_carries_the_ssd_and_conv_scopes():
    tr = ElasticTrainer(program.model_config(GRANITE),
                        TrainJobConfig(global_batch=B, seq_len=S,
                                       total_steps=10),
                        jax.devices()[:1])
    text = tr.compiled_step.as_text()
    for scope in ("mixer/ssd", "mixer/conv"):
        assert scope in text, scope


def _remats(jaxpr) -> int:
    """Checkpoint equations in ``jaxpr`` and the bodies it calls (scans,
    nested jits), not counting those inside another checkpoint."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("checkpoint", "remat2"):
            n += 1
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _remats(sub)
    return n


# GRANITE: one scanned block of an SSM and an attention layer, each held on
# its own in train; the SSD chunk body is checkpointed in every mode (in
# train, inside its layer's checkpoint).  DENSE: the scanned block is one
# layer.
@pytest.mark.parametrize("conf, per_layer, always", [(GRANITE, 2, 1),
                                                     (DENSE, 1, 0)])
def test_train_remats_each_layer_and_serving_does_not(conf, per_layer,
                                                      always):
    cfg = program.model_config(conf)
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    counts = {mode: _remats(jax.make_jaxpr(
        lambda p, t: forward_hidden(cfg, p, {"tokens": t}, mode=mode))(
            abstract_params(cfg), tok).jaxpr)
        for mode in ("train", "prefill")}
    assert counts == {"train": per_layer, "prefill": always}


def test_environment_selects_no_kernel():
    # the model step reads no environment: with a kernel-selecting
    # variable set, the train step's lowering holds the jnp SSD scope and
    # no ssd_scan call
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.configs import smoke_config\n"
        "from repro.models import abstract_params, loss_fn\n"
        "cfg = smoke_config('granite-4.0-h-micro').with_(dtype='float32')\n"
        "t = jax.ShapeDtypeStruct((1, 32), jnp.int32)\n"
        "g = jax.grad(lambda p, t: loss_fn(cfg, p, {'tokens': t, "
        "'labels': t})[0])\n"
        "text = jax.jit(g).lower(abstract_params(cfg), t).as_text("
        "debug_info=True)\n"
        "print('ssd_scan' in text, 'mixer/ssd' in text)\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, REPRO_USE_PALLAS="1", JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(root, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["False", "True"], out.stdout


def test_tiny_granite_cell_runs_correct_through_the_harness():
    # the cell's own files and layout (8 layers, attention at 5, all held
    # one by one), at widths and a sequence a CPU test run holds
    cell = copy.deepcopy(spec.resolve("granite4h.train4k"))
    cell.config.update(d_model=64, num_heads=2, num_kv_heads=1, head_dim=32,
                       d_ff=128, vocab_size=256,
                       ssm=dict(GRANITE["ssm"]))
    cell.traffic["seq_len"] = S
    cell.limits = {"loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-2}
    assert [granite_hybrid.is_attention(cell.config, i)
            for i in range(8)] == [False] * 5 + [True, False, False]
    r = driver.run_cell(cell, SEED, 0.5, False, jax.devices()[:1],
                        time.perf_counter())
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("n_layers, prefix", [(8, 8), (10, 0), (20, 0)])
def test_reference_layout_is_the_programs(n_layers, prefix):
    conf = dict(spec.load_json(
        os.path.join(spec.BENCH, "configs", "granite4h_micro.json")),
        num_layers=n_layers)
    cfg = program.model_config(conf)
    assert cfg.scan_layers()[0] == prefix
    leaves, _, _ = program.flat(abstract_params(cfg))
    assert {p: tuple(x.shape) for p, x in leaves.items()} == \
        granite_hybrid.param_shapes(conf)
