"""Pieces both transformer references share: layer norm, the tanh GELU,
softmax cross-entropy, all in float32."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def cross_entropy(logits, labels):
    """Per-position -log softmax(logits)[label], float32."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - picked


def dense(dot, x, p):
    return dot.einsum("...i,io->...o", x, p["kernel"]) + p["bias"]


def attention(dot, q, k, v, mask):
    """Softmax attention, (B, T, H, D) operands; ``mask`` broadcasts to
    (B, H, Tq, Tk) and is True where a key may be read."""
    scores = dot.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return dot.einsum("bhqk,bkhd->bqhd", probs, v)
