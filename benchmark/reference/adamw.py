"""The optimizer the training cells state, written out: global-norm
clipping, Adam moments with bias correction, decoupled weight decay, and a
learning rate that warms up linearly from zero.  Only the warm-up is
written: the reference follows a run's first steps and refuses more."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def clip_by_global_norm(grads, max_norm, eps=1e-6):
    """-> (clipped gradients, the norm before clipping)."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (norm + eps))
    return jax.tree.map(lambda g: g * scale, grads), norm


def init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32)}


def learning_rate(opt, count):
    """Linear warm-up from 0 to ``peak_lr`` over ``warmup_steps``."""
    return float(opt["peak_lr"]) * count / float(opt["warmup_steps"])


def update(opt, params, state, grads):
    """One step on already clipped gradients -> (params, state)."""
    b1, b2 = float(opt["b1"]), float(opt["b2"])
    count = state["count"]
    lr = learning_rate(opt, count.astype(jnp.float32))
    step = (count + 1).astype(jnp.float32)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                      state["nu"], grads)

    def new_param(p, m, v):
        direction = (m / (1 - b1 ** step)) / (
            jnp.sqrt(v / (1 - b2 ** step)) + float(opt["eps"]))
        return p - lr * (direction + float(opt["weight_decay"]) * p)

    return (jax.tree.map(new_param, params, mu, nu),
            {"mu": mu, "nu": nu, "count": count + 1})
