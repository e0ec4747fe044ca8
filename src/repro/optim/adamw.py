"""AdamW with global-norm clipping (pure functions, fp32 moments).

Moments inherit each parameter's logical axes, so they shard exactly like the
parameter they track (ZeRO-like: with FSDP rules the optimizer state is fully
sharded too).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamWConfig:
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


def adamw_init(params) -> dict:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "count": jnp.zeros((), jnp.int32),
    }


def abstract_opt_state(abstract_params) -> dict:
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    return {
        "m": jax.tree.map(f32, abstract_params),
        "v": jax.tree.map(f32, abstract_params),
        "count": jax.ShapeDtypeStruct((), jnp.int32),
    }


def opt_logical_axes(param_axes) -> dict:
    ident = lambda a: a
    is_leaf = lambda l: isinstance(l, tuple)
    return {
        "m": jax.tree.map(ident, param_axes, is_leaf=is_leaf),
        "v": jax.tree.map(ident, param_axes, is_leaf=is_leaf),
        "count": (),
    }


def global_norm(tree) -> jax.Array:
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
             for x in jax.tree.leaves(tree))
    return jnp.sqrt(sq)


@jax.named_scope("optimizer")
def adamw_update(cfg: AdamWConfig, grads, state, params, lr):
    """Returns (new_params, new_state, metrics); the global-norm clip is
    inside the ``optimizer`` scope too."""
    count = state["count"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))

    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** count.astype(jnp.float32)
    bc2 = 1.0 - b2 ** count.astype(jnp.float32)

    def upd(g, m, v, p):
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mh = m / bc1
        vh = v / bc2
        step = mh / (jnp.sqrt(vh) + cfg.eps)
        p32 = p.astype(jnp.float32)
        p32 = p32 - lr * (step + cfg.weight_decay * p32)
        return p32.astype(p.dtype), m, v

    flat_g, treedef = jax.tree.flatten(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    flat_p = treedef.flatten_up_to(params)
    out = [upd(g, m, v, p) for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    new_state = {"m": new_m, "v": new_v, "count": count}
    return new_p, new_state, {"grad_norm": gnorm, "lr": lr}
