"""Weights made from the run's seed on the device, in one jitted call.

A configuration's model module gives the layout (``param_shapes``: path ->
shape) and the rule each leaf is drawn by (``init_rule``).  Leaf ``i`` in the
sorted order of paths draws from ``fold_in(key(seed), i)``, so the program
and the reference, which both call :func:`make`, get the same values
whatever the sharding.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int):
    """A threefry key from any whole-number seed (more than 32 bits too)."""
    words = np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def draw(rule: Tuple, key, shape) -> jax.Array:
    kind = rule[0]
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * rule[1]
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, rule[1], rule[2])
    if kind == "log_uniform":            # log of U(lo, hi)
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          rule[1], rule[2]))
    if kind == "softplus_inv_log_uniform":
        # dt = exp(U(log lo, log hi)); the bias is softplus^-1(dt)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(rule[1]), math.log(rule[2])))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(f"unknown init rule {rule!r}")


def generator(shapes: Dict[str, tuple], rule_of: Callable,
              dtype=jnp.float32) -> Callable:
    """``gen(key) -> {path: array}``, traceable, in ``dtype``."""
    order = sorted(shapes)

    def gen(key):
        return {p: draw(rule_of(p, shapes[p]), jax.random.fold_in(key, i),
                        shapes[p]).astype(dtype)
                for i, p in enumerate(order)}
    return gen


def make(shapes: Dict[str, tuple], rule_of: Callable, seed: int,
         shardings: Optional[Dict[str, object]] = None,
         dtype=jnp.float32) -> Dict[str, jax.Array]:
    gen = generator(shapes, rule_of, dtype)
    jitted = jax.jit(gen) if shardings is None else jax.jit(
        gen, out_shardings=shardings)
    return jitted(base_key(seed))
