"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: each case lowers and compiles at real widths for a chip that is
described, not attached, and so catches what interpret mode cannot (block
shapes the TPU's tiling refuses, VMEM overruns).  The topology is described
inside a fixture, so only the worker that runs this file loads the TPU
compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.pack import pack_leaves_pallas


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_compiles_at_yi6b_heads(one_chip, dtype):
    # yi-6b: 32 query heads, 4 KV heads, head_dim 128; seq 2048
    q = ((1, 2048, 32, 128), dtype)
    kv = ((1, 2048, 4, 128), dtype)
    _compile(lambda q, k, v: ops.flash_attention(q, k, v),
             one_chip, q, kv, kv)


def test_flash_attention_grad_compiles_at_yi6b_train_shape(one_chip):
    # the benchmark's train shape: batch 4 x 2048, 32 query / 4 KV heads
    q = ((4, 2048, 32, 128), jnp.float32)
    kv = ((4, 2048, 4, 128), jnp.float32)
    grad = jax.grad(lambda q, k, v: jnp.sum(ops.flash_attention(q, k, v)),
                    (0, 1, 2))
    _compile(grad, one_chip, q, kv, kv)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_compiles_at_granite4h_heads(one_chip, dtype):
    # granite-4.0-h: 32 query heads, 8 KV heads, head_dim 64; the
    # granite4h.train4k cell's batch 2 x 4096
    q = ((2, 4096, 32, 64), dtype)
    kv = ((2, 4096, 8, 64), dtype)
    _compile(lambda q, k, v: ops.flash_attention(q, k, v, scale=0.015625),
             one_chip, q, kv, kv)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_grad_compiles_at_granite4h_heads(one_chip, dtype):
    q = ((2, 4096, 32, 64), dtype)
    kv = ((2, 4096, 8, 64), dtype)
    grad = jax.grad(lambda q, k, v: jnp.sum(ops.flash_attention(
        q, k, v, scale=0.015625).astype(jnp.float32)), (0, 1, 2))
    _compile(grad, one_chip, q, kv, kv)


def test_yi6b_train_step_holds_the_flash_kernels(one_chip):
    from repro.configs import get_config
    from repro.models import loss_fn
    from repro.models.params import abstract_params
    cfg = get_config("yi-6b").with_(num_layers=2, vocab_size=8000,
                                    dtype="float32", expected_params=0.0)

    def grads(p, batch):
        return jax.grad(lambda p: loss_fn(cfg, p, batch)[0])(p)

    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        abstract_params(cfg))
    tokens = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=one_chip)
    text = jax.jit(grads).lower(
        params, {"tokens": tokens, "labels": tokens}).as_text()
    for name in ("flash_attention_fwd", "flash_attention_dkv",
                 "flash_attention_dq"):
        assert name in text, name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_pack_kernel_compiles(one_chip, dtype):
    shapes = [((4096, 4096), dtype), ((4096,), dtype), ((11008, 37), dtype)]
    _compile(lambda *leaves: pack_leaves_pallas(list(leaves)), one_chip,
             *shapes)


def test_ssd_compiles_at_mamba2_widths(one_chip):
    # mamba2-1.3b: d_inner 4096 = 64 heads x head_dim 64, d_state 128,
    # one B/C group, chunk 128
    B, L, H, P, G, N = 1, 2048, 64, 64, 1, 128
    _compile(lambda x, dt, a, b, c: ops.ssd(x, dt, a, b, c, chunk=128),
             one_chip, ((B, L, H, P), jnp.float32), ((B, L, H), jnp.float32),
             ((H,), jnp.float32), ((B, L, G, N), jnp.float32),
             ((B, L, G, N), jnp.float32))


def test_granite4h_train_step_fits_one_chip(one_chip):
    # the granite4h.train4k cell's step: its configuration file, f32
    # weights with AdamW state donated, batch 2 x 4096
    import json

    from bench.harness import program
    from repro.models import loss_fn
    from repro.models.params import abstract_params
    from repro.optim import AdamWConfig, abstract_opt_state, adamw_update
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "bench", "configs",
                           "granite4h_micro.json")) as f:
        cfg = program.model_config(json.load(f))

    def step(p, o, batch):
        grads = jax.grad(lambda p: loss_fn(cfg, p, batch)[0])(p)
        p, o, _ = adamw_update(AdamWConfig(), grads, o, p, 3e-4)
        return p, o

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)
    params = on_chip(abstract_params(cfg))
    tokens = jax.ShapeDtypeStruct((2, 4096), jnp.int32, sharding=one_chip)
    lowered = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, on_chip(abstract_opt_state(abstract_params(cfg))),
        {"tokens": tokens, "labels": tokens})
    # its hd-64 attention layer takes the flash kernels
    text = lowered.as_text()
    for name in ("flash_attention_fwd", "flash_attention_dkv",
                 "flash_attention_dq"):
        assert name in text, name
    mem = lowered.compile().memory_analysis()
    assert cfg.dtype == "float32" and cfg.num_layers == 8
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes <= 14e9
