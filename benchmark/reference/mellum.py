"""A grouped-query decoder of window and full attention layers by turns,
every MLP a layer of sparse experts (``model_type`` ``mellum``;
Mellum2-12B-A2.5B-Instruct's ``config.json``), the plain reference.

Written from the configuration's keys; every product goes through
``dot.einsum``; no cache, no kernel, no batching of requests beyond the
rows it is given.

    h = x + Attn_l(RMSNorm(x));  y = h + MoE(RMSNorm(h));  eps
    ``rms_norm_eps``; no bias anywhere; final RMSNorm; untied head.

Attention, layer ``l``: ``q = x W_q`` (``num_attention_heads`` heads of
``head_dim``), ``k = x W_k``, ``v = x W_v`` (``num_key_value_heads``
heads); rotary positions over the whole head, dimension ``i`` with ``i +
head_dim / 2``; K/V head ``g`` serves query heads ``g G .. g G + G - 1``
(``G`` the ratio of the two head counts: the K/V heads are repeated
here); scores ``q . k / sqrt(head_dim)``, softmax, the heads' outputs
through ``W_o``.  The mask is causal, and where ``layer_types[l]`` is
``sliding_attention`` a query at ``i`` also reads only keys with ``i - j
< sliding_window``.  The full ``(T, T)`` scores are taken a block of
queries at a time (``QUERY_BLOCK``), so that a row of some thousand
positions fits; every query still sees every key its mask allows.

Rotary table: window layers ``inv_freq_i = theta ** (-2i / D)``.  Full
layers YaRN (``rope_parameters.full_attention``): ``extra_i = theta **
(-2i / D)``, ``inter_i = extra_i / factor``; ``low``, ``high`` = floor,
ceil of ``D ln(original_max_position_embeddings / (2 pi b)) / (2 ln
theta)`` at ``b`` = ``beta_fast``, ``beta_slow``, clipped to ``0 .. D -
1``; ``ramp_i = clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i =
inter_i ramp_i + extra_i (1 - ramp_i)``; cos and sin times
``attention_factor``.

MoE: ``p = softmax(x W_r)`` over the ``num_experts_published`` experts;
the ``num_experts_per_tok`` largest chosen (lower index first on a tie);
``w = p[chosen] / sum(p[chosen])`` (``norm_topk_prob``); ``y = sum_e w_e
E_e(x)``, ``E(x) = W_d(silu(W_g x) * W_u x)`` at
``moe_intermediate_size``; no shared expert.

**The share.**  ``cfg["num_experts"]`` experts are held, numbers
``first_expert_held ..``, of the ``num_experts_published`` the router
scores.  The sum runs over the held experts alone (one after the other,
each over every token with its weight zero where the router chose
otherwise); what the absent experts would add is left out, as on the chip
that holds this share.

Weights, in the tree of the benchmark's draw: ``embed`` (V, d); ``head``
(d, V); ``final_norm``; ``layers`` with every leaf stacked on a leading
layer dimension: ``input_norm``, ``post_norm``, ``attn`` (``q``, ``k``,
``v``, ``o``), ``router`` (``kernel``), ``experts`` (``gate_up`` (held, 2
x width, d): W_g's rows, then W_u's; ``down`` (held, width, d)).

Departures, which the configuration file lists: the parameters are the
draw rounded to ``parameter_dtype`` (the arithmetic here is float32 on
those values); rotate-half pairing; the window counts the query itself;
no per-head norm on q and k, no attention sink; no multi-token head.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


def _as_held(cfg, tree):
    """The draw as the model holds it: rounded to the parameter type's
    values, in float32 (no array of the narrow type: see
    ``reference/glm4_moe_lite.py``)."""
    info = jnp.finfo(jnp.dtype(cfg["parameter_dtype"]))
    return jax.tree.map(
        lambda x: jax.lax.reduce_precision(x, info.nexp, info.nmant), tree)


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def rotary_table(cfg, layer_type):
    """-> (inv_freq (D / 2,), the factor on cos and sin)."""
    dim = int(cfg["head_dim"])
    rp = cfg["rope_parameters"][layer_type]
    theta = float(rp["rope_theta"])
    i = np.arange(dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / dim)
    if rp["rope_type"] == "default":
        return extra.astype(np.float32), 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r} is not written here")
    inter = extra / float(rp["factor"])
    turns = lambda b: (dim * math.log(
        float(rp["original_max_position_embeddings"]) / (2 * math.pi * b))
        / (2 * math.log(theta)))
    low = max(math.floor(turns(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(turns(float(rp["beta_slow"]))), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return ((inter * ramp + extra * (1.0 - ramp)).astype(np.float32),
            float(rp["attention_factor"]))


def _rope(x, inv_freq, factor):
    """(B, T, H, D) with position t at axis 1; pairs (i, i + D/2)."""
    t, half = x.shape[1], x.shape[-1] // 2
    angle = (np.arange(t, dtype=np.float32)[:, None]
             * inv_freq[None, :]).reshape(1, t, 1, half)
    cos, sin = np.cos(angle) * factor, np.sin(angle) * factor
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(dot, cfg, x, p, layer_type):
    b, t, _ = x.shape
    h, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    dim = int(cfg["head_dim"])
    window = (int(cfg["sliding_window"])
              if layer_type == "sliding_attention" else None)
    table = rotary_table(cfg, layer_type)
    q = _rope(dot.einsum("btd,df->btf", x, p["q"]["kernel"]).reshape(
        b, t, h, dim), *table)
    k = _rope(dot.einsum("btd,df->btf", x, p["k"]["kernel"]).reshape(
        b, t, hkv, dim), *table)
    v = dot.einsum("btd,df->btf", x, p["v"]["kernel"]).reshape(b, t, hkv, dim)
    k, v = (jnp.repeat(a, h // hkv, axis=2) for a in (k, v))
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def some_queries(args):
        qb, first = args                                   # (B, block, H, D)
        scores = dot.einsum("bqhd,bkhd->bhqk", qb, k) / np.sqrt(dim)
        i = first + jnp.arange(block)[:, None]
        j = jnp.arange(t)[None, :]
        ok = j <= i
        if window is not None:
            ok = ok & (i - j < window)
        probs = jax.nn.softmax(jnp.where(ok[None, None], scores, -1e30),
                               axis=-1)
        return dot.einsum("bhqk,bkhd->bqhd", probs, v)

    ctx = jax.lax.map(some_queries, (
        jnp.moveaxis(q.reshape(b, t // block, block, h, dim), 1, 0),
        jnp.arange(t // block) * block))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, t, h * dim)
    return dot.einsum("btf,fd->btd", ctx, p["o"]["kernel"])


def _mlp(dot, x, p):
    """``gate_up`` holds W_g's rows and then W_u's, each (width, d)."""
    g, u = jnp.split(
        dot.einsum("...d,gd->...g", x, p["gate_up"]["kernel"]), 2, axis=-1)
    return dot.einsum("...f,fd->...d", jax.nn.silu(g) * u, p["down"]["kernel"])


def _route(dot, cfg, x, p):
    """-> (N, published experts) weights, zero where not chosen."""
    probs = jax.nn.softmax(dot.einsum("nd,de->ne", x, p["kernel"]), axis=-1)
    chosen = jnp.zeros(probs.shape, bool)
    for _ in range(int(cfg["num_experts_per_tok"])):
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, probs), axis=-1)
        chosen = chosen | jax.nn.one_hot(best, probs.shape[-1], dtype=bool)
    weights = jnp.where(chosen, probs, 0.0)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights


def expert_ffn(dot, cfg, x, p):
    """(N, d) -> the held experts' part of the routed result."""
    weights = _route(dot, cfg, x, p["router"])
    first = int(cfg.get("first_expert_held", 0))
    held = weights[:, first:first + int(cfg["num_experts"])]

    def add_one(y, expert_and_weight):      # one held expert after the other
        expert, w = expert_and_weight
        return y + w[:, None] * _mlp(dot, x, expert), None

    y, _ = jax.lax.scan(add_one, jnp.zeros_like(x), (p["experts"], held.T))
    return y


def _layer(dot, cfg, x, p, layer_type):
    eps = float(cfg["rms_norm_eps"])
    p = _as_held(cfg, p)
    h = x + _attention(dot, cfg, _rms_norm(x, p["input_norm"], eps),
                       p["attn"], layer_type)
    hn = _rms_norm(h, p["post_norm"], eps)
    b, t, d = hn.shape
    return h + expert_ffn(dot, cfg, hn.reshape(b * t, d), p).reshape(b, t, d)


def layer_types(cfg):
    """The kinds of the layers that are run: the published list's first
    ``num_hidden_layers`` (a cut in depth keeps whole periods)."""
    return list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]


def logits(dot, cfg, params, tokens):
    """(B, T) token ids -> (B, T, V) float32 next-token logits."""
    types = layer_types(cfg)
    n = len(types)
    period = next(p for p in range(1, n + 1)
                  if n % p == 0 and types == types[:p] * (n // p))
    x = _as_held(cfg, {"embed": params["embed"][tokens]})["embed"]

    def one_period(x, ps):                   # the pattern's layers, in turn
        for i in range(period):
            x = _layer(dot, cfg, x, jax.tree.map(lambda w: w[i], ps),
                       types[i])
        return x, None

    x, _ = jax.lax.scan(one_period, x, jax.tree.map(
        lambda w: w.reshape((n // period, period) + w.shape[1:]),
        params["layers"]))
    x = _rms_norm(x, _as_held(cfg, params["final_norm"]),
                  float(cfg["rms_norm_eps"]))
    return dot.einsum("btd,dv->btv", x,
                      _as_held(cfg, params["head"])["kernel"])
