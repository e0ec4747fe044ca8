"""The benchmark's one point of contact with the system under test.

It builds the program's ``ElasticTrainer`` as the configuration file states
it, then gives it the benchmark's inputs: the token stream made from the
seed, and weights made from the seed in place of the trainer's own.  It
reads back only what the program already exposes: the state arrays and the
rescale timings.
"""
from __future__ import annotations

import dataclasses
import os
import sys
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench.harness import traffic as traffic_mod
from bench.harness import weights
from bench.harness.spec import ROOT

SRC = os.path.join(ROOT, "src")
# keys of a configuration file that share a name with a ModelConfig field
# but describe the file rather than size the model
_NOT_SIZES = {"family", "source"}


def _program():
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.configs import get_config
    from repro.core.elastic import ElasticTrainer, TrainJobConfig
    return get_config, ElasticTrainer, TrainJobConfig


def model_config(conf: dict):
    """The program's ModelConfig with every size the file states."""
    get_config, _, _ = _program()
    cfg = get_config(conf["program_arch"])
    kw = {k: v for k, v in conf.items()
          if k not in _NOT_SIZES
          and k in {f.name for f in dataclasses.fields(cfg)}}
    if "ssm" in kw:
        kw["ssm"] = dataclasses.replace(cfg.ssm, **kw["ssm"])
    return cfg.with_(expected_params=0.0, **kw)


def build_trainer(conf: dict, traffic: dict, devices):
    _, ElasticTrainer, TrainJobConfig = _program()
    opt = conf["optimizer"]
    job = TrainJobConfig(global_batch=traffic["global_batch"],
                         seq_len=traffic["seq_len"],
                         total_steps=opt["total_steps"],
                         warmup_steps=opt["warmup_steps"],
                         peak_lr=opt["peak_lr"], seed=0, dtype=conf["dtype"])
    tr = ElasticTrainer(model_config(conf), job, devices)
    for k in ("b1", "b2", "eps", "weight_decay", "clip_norm"):
        if getattr(tr.adamw, k) != opt[k]:
            raise ValueError(f"the program's AdamW {k} is "
                             f"{getattr(tr.adamw, k)}, the configuration "
                             f"states {opt[k]}")
    return tr


def _path(kp) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)


def flat(tree) -> Tuple[Dict[str, jax.Array], List[str], object]:
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = [_path(kp) for kp, _ in leaves]
    return dict(zip(paths, (x for _, x in leaves))), paths, treedef


def install(tr, model, conf: dict, traffic: dict, seed: int, span) -> None:
    """Give the trainer the benchmark's token stream and weights."""
    tr.stream = traffic_mod.stream_for(traffic, conf["vocab_size"], seed, span)
    leaves, paths, treedef = flat(tr.params)
    shapes = model.param_shapes(conf)
    held = {p: tuple(x.shape) for p, x in leaves.items()}
    if held != shapes:
        raise ValueError(f"the program's parameter layout {held} is not the "
                         f"benchmark's {shapes}")
    dtype = jnp.dtype(conf["dtype"])
    new = weights.make(shapes, model.init_rule, seed,
                       {p: x.sharding for p, x in leaves.items()}, dtype)
    tr.params = jax.tree_util.tree_unflatten(treedef, [new[p] for p in paths])


def _bits(x):
    u = jax.lax.bitcast_convert_type(x, jnp.uint32).ravel()
    w = jax.lax.iota(jnp.uint32, u.size) * jnp.uint32(2654435761) + 1
    return jnp.sum(u * w, dtype=jnp.uint32)


@jax.jit
def fingerprint(params, opt_state):
    """One position-weighted sum of the bits of each leaf, modulo 2**32."""
    return jnp.stack([_bits(x) for x in jax.tree.leaves((params, opt_state))])


def first_grad_norms(tr, b1: float) -> Dict[str, float]:
    """Per leaf, the norm of the clipped gradient of the first step, worked
    out from AdamW's first moment after it: m1 = (1 - b1) g."""
    m, _, _ = flat(tr.opt_state["m"])
    out = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v))) / (1 - b1)
                             for k, v in t.items()})(m)
    return {k: float(v) for k, v in out.items()}


def change_norms(tr, model, conf: dict, seed: int) -> Dict[str, float]:
    """Per leaf, the norm of the change from the seed's weights."""
    p, _, _ = flat(tr.params)
    gen = weights.generator(model.param_shapes(conf), model.init_rule)

    def norms(t, key):
        w0 = gen(key)
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32) - w0[k])))
                for k, v in t.items()}
    out = jax.jit(norms)(p, weights.base_key(seed))
    return {k: float(v) for k, v in out.items()}
