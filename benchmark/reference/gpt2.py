"""GPT-2 (Radford et al. 2019), the plain reference.

Pre-LN decoder: x += attn(ln_1(x)); x += mlp(ln_2(x)); learned token and
position embeddings; fused qkv projection; tanh GELU (``gelu_new``);
final layer norm; output head tied to the token embedding; loss = mean
next-token cross-entropy over positions 0..T-2.

Weights: ``wte`` (V, d), ``wpe`` (P, d), ``ln_f``, and ``blocks`` with every
layer's leaves stacked on a leading layer dimension: ``ln_1``, ``c_attn``
(d, 3d), ``c_proj`` (d, d), ``ln_2``, ``mlp_c_fc`` (d, 4d), ``mlp_c_proj``.

Departures from the published model, which the configuration file lists:
the layer-norm epsilon is the one the configuration states it runs with,
and no dropout is applied.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common


def _block(dot, cfg, x, p):
    b, t, d = x.shape
    h = int(cfg["n_head"])
    eps = float(cfg["layer_norm_epsilon"])
    y = common.layer_norm(x, p["ln_1"], eps)
    q, k, v = jnp.split(common.dense(dot, y, p["c_attn"]), 3, axis=-1)
    shape = (b, t, h, d // h)
    causal = jnp.tril(jnp.ones((t, t), bool))[None, None]
    ctx = common.attention(dot, q.reshape(shape), k.reshape(shape),
                           v.reshape(shape), causal).reshape(b, t, d)
    x = x + common.dense(dot, ctx, p["c_proj"])
    y = common.layer_norm(x, p["ln_2"], eps)
    y = common.gelu_tanh(common.dense(dot, y, p["mlp_c_fc"]))
    return x + common.dense(dot, y, p["mlp_c_proj"])


def logits(dot, cfg, params, tokens):
    """(B, T) token ids -> (B, T, V) float32 next-token logits."""
    t = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][:t]

    def layer(x, p):
        return _block(dot, cfg, x, p), None

    # A scan over the stacked layers, each recomputed in the backward pass:
    # the same mathematics as a loop, at one layer's activations.
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["blocks"])
    x = common.layer_norm(x, params["ln_f"], float(cfg["layer_norm_epsilon"]))
    return dot.einsum("btd,vd->btv", x, params["wte"])


def loss(dot, cfg, params, batch):
    """Mean next-token cross-entropy over this block of rows."""
    tokens = batch["tokens"]
    out = logits(dot, cfg, params, tokens)
    return jnp.mean(common.cross_entropy(out[:, :-1], tokens[:, 1:]))
