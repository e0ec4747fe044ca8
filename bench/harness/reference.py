"""The plain reference of a training cell's first steps.

It makes the weights from the seed, reads the same batches as the program,
and takes each step with a plain AdamW: the gradient of the batch's mean
loss is accumulated over blocks of rows so that the reference fits on one
chip, then clipped by its global norm.  It returns what the comparison
reads: each step's loss, the norm of each leaf of the first (clipped)
gradient, and the norm of each leaf's change after the last step.

``dtype`` and ``precision`` give the control (bfloat16 in the program's
place); ``rows_of_step`` plants a fault (rows left out of a step's mean).
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import traffic as traffic_mod
from bench.harness import weights


def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up to ``peak_lr``, then cosine to ``min_lr_ratio``."""
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return peak * min(1.0, (step + 1) / max(warm, 1))
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    r = opt["min_lr_ratio"]
    return peak * (r + (1 - r) * 0.5 * (1 + np.cos(np.pi * t)))


def leaf_norms(tree: Dict[str, jax.Array]) -> Dict[str, float]:
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})(tree)
    return {k: float(v) for k, v in norms.items()}


def _adamw(opt, p, m, v, g, count, lr, param_dtype):
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    g = {k: x * scale for k, x in g.items()}
    b1, b2 = opt["b1"], opt["b2"]
    m = {k: b1 * m[k] + (1 - b1) * g[k] for k in g}
    v = {k: b2 * v[k] + (1 - b2) * jnp.square(g[k]) for k in g}
    bc1, bc2 = 1 - b1 ** count, 1 - b2 ** count
    new = {}
    for k in g:
        w = p[k].astype(jnp.float32)
        upd = (m[k] / bc1) / (jnp.sqrt(v[k] / bc2) + opt["eps"])
        new[k] = (w - lr * (upd + opt["weight_decay"] * w)).astype(param_dtype)
    return new, m, v, g


def run(model, config: dict, traffic: dict, seed: int, steps: int = 3, *,
        dtype=jnp.float32, precision: Optional[str] = "highest",
        rows_of_step: Optional[Callable[[int, int], range]] = None) -> dict:
    opt = config["optimizer"]
    shapes = model.param_shapes(config)
    stream = traffic_mod.stream_for(traffic, config["vocab_size"], seed)
    ctx = (jax.default_matmul_precision(precision) if precision
           else contextlib.nullcontext())
    with ctx:
        grad = jax.jit(jax.value_and_grad(
            lambda p, t, l: model.row_loss_sum(config, p, t, l, dtype)))
        add = jax.jit(lambda a, b: {k: a[k] + b[k].astype(jnp.float32)
                                    for k in a}, donate_argnums=0)
        adamw = jax.jit(lambda p, m, v, g, c, lr: _adamw(
            opt, p, m, v, g, c, lr, dtype), donate_argnums=(0, 1, 2, 3))
        p = weights.make(shapes, model.init_rule, seed, dtype=dtype)
        m = {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()}
        v = {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()}
        losses, first_grad = [], None
        for s in range(steps):
            batch = stream.global_batch_at(s)
            B, S = batch["tokens"].shape
            rows = rows_of_step(s, B) if rows_of_step else range(B)
            total, g = 0.0, None
            for r in rows:
                loss, gr = grad(p, batch["tokens"][r], batch["labels"][r])
                total += float(loss)
                g = ({k: x.astype(jnp.float32) for k, x in gr.items()}
                     if g is None else add(g, gr))
            n = len(rows) * S
            g = jax.jit(lambda g: {k: x / n for k, x in g.items()},
                        donate_argnums=0)(g)
            losses.append(total / n)
            p, m, v, gc = adamw(p, m, v, g, jnp.float32(s + 1),
                                jnp.float32(lr_at(opt, s)))
            if s == 0:
                first_grad = leaf_norms(gc)
            del g, gc
        p0 = weights.make(shapes, model.init_rule, seed)
        change = leaf_norms(jax.jit(lambda a, b: {
            k: a[k].astype(jnp.float32) - b[k] for k in a})(p, p0))
    return {"losses": losses, "first_grad": first_grad, "change": change}
