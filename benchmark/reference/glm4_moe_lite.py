"""A latent-attention, sparse-expert decoder (``model_type``
``glm4_moe_lite``; GLM-4.7-Flash's ``config.json``), the plain reference.

Written from the configuration's keys; every product goes through
``dot.einsum``; no cache, no kernel, no batching of requests beyond the
rows it is given.

    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h));  final RMSNorm;
    untied head.

Attention (MLA), per layer: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` ->
heads of (``qk_nope_head_dim`` | ``qk_rope_head_dim``); ``[c_kv | k_r] = x
W_kva``; ``c_kv = RMSNorm(c_kv)``; ``k_r = RoPE(k_r)``, one for all heads;
``[k_n | v] = c_kv W_kvb`` -> heads of (``qk_nope_head_dim`` |
``v_head_dim``); ``q = [q_n | RoPE(q_r)]``, ``k = [k_n | k_r]``; causal
softmax of ``q . k / sqrt(qk_nope_head_dim + qk_rope_head_dim)``; the
heads' outputs through ``W_o``.  Keys and values are expanded in full
here: nothing is absorbed and nothing is cached.

FFN: the first ``first_k_dense_replace`` layers a gated MLP ``W_d(silu(W_g
x) * W_u x)`` of ``intermediate_size``; every later layer ``s = sigmoid(x
W_r)``; the ``num_experts_per_tok`` largest of ``s + b`` chosen (``b`` the
correction bias, used for the choice alone); ``w = s[chosen] /
sum(s[chosen]) * routed_scaling_factor``; ``y = sum_e w_e E_e(x) +
E_shared(x)``, each expert the gated MLP at ``moe_intermediate_size``.

**The share.**  ``cfg["n_routed_experts"]`` experts are held, numbers
``first_expert_held ..``, of the ``n_routed_experts_published`` the router
scores.  The sum runs over the held experts alone (looped over, one after
the other, each over every token with its weight zero where the router
chose otherwise); what the absent experts would add is left out, as on
the chip that holds this share.

Weights, in the tree of the benchmark's draw: ``embed`` (V, d); ``head``
(d, V); ``final_norm``; ``dense_layers`` and ``moe_layers`` with every
leaf stacked on a leading layer dimension: ``input_norm``, ``post_norm``,
``attn`` (``q_a``, ``q_a_norm``, ``q_b``, ``kv_a``, ``kv_a_norm``,
``kv_b``, ``o``), and ``mlp`` (dense) or ``router`` (``kernel``, ``bias``),
``shared``, ``experts`` (``gate_up`` (held, 2 x width, d): W_g's rows, then
W_u's; ``down`` (held, width, d)).

Departures, which the configuration file lists: the parameters are the
draw rounded to ``parameter_dtype`` (the model's weights are held in that
type; the arithmetic here is float32 on those values), the correction
bias stays float32; rotary pairs are (i, i + half); no multi-token-
prediction layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _as_held(cfg, tree):
    """The draw as the model holds it: rounded to the parameter type's
    values (the router's correction bias excepted), in float32.
    ``reduce_precision`` gives the values of a cast there and back
    (round to nearest even) without an array of the narrow type, which
    the compiler would hoist out of the layer loop as a second copy of
    every weight (4.6 GB of scratch at the cell's size, 0.6 GB so)."""
    dtype = jnp.dtype(cfg["parameter_dtype"])

    def one(path, x):
        names = [getattr(k, "key", None) for k in path]
        if names[-2:] == ["router", "bias"]:
            return x
        info = jnp.finfo(dtype)      # in float32 throughout: no narrow copy
        return jax.lax.reduce_precision(x, info.nexp, info.nmant)

    return jax.tree_util.tree_map_with_path(one, tree)


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def _rope(x, theta):
    """(B, T, ..., D) with position t at axis 1; pairs (i, i + D/2)."""
    t, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    angle = np.arange(t, dtype=np.float32)[:, None] * freq[None, :]
    angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (half,))
    cos, sin = np.cos(angle), np.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _mlp(dot, x, p):
    """``gate_up`` holds W_g's rows and then W_u's, each (width, d)."""
    g, u = jnp.split(
        dot.einsum("...d,gd->...g", x, p["gate_up"]["kernel"]), 2, axis=-1)
    return dot.einsum("...f,fd->...d", jax.nn.silu(g) * u, p["down"]["kernel"])


def _attention(dot, cfg, x, p):
    b, t, _ = x.shape
    h = int(cfg["num_attention_heads"])
    nope, rot = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    vd, rank = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    c_q = _rms_norm(dot.einsum("btd,dr->btr", x, p["q_a"]["kernel"]),
                    p["q_a_norm"], eps)
    q = dot.einsum("btr,rf->btf", c_q, p["q_b"]["kernel"]).reshape(
        b, t, h, nope + rot)
    kva = dot.einsum("btd,dc->btc", x, p["kv_a"]["kernel"])
    c_kv = _rms_norm(kva[..., :rank], p["kv_a_norm"], eps)
    k_r = _rope(kva[..., rank:], theta)                          # (B, T, rot)
    kv = dot.einsum("btc,cf->btf", c_kv, p["kv_b"]["kernel"]).reshape(
        b, t, h, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r[:, :, None], (b, t, h, rot))],
        axis=-1)
    v = kv[..., nope:]
    scores = dot.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(nope + rot)
    scores = jnp.where(np.tril(np.ones((t, t), bool))[None, None], scores,
                       -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = dot.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, h * vd)
    return dot.einsum("btf,fd->btd", ctx, p["o"]["kernel"])


def _route(dot, cfg, x, p):
    """-> (N, published experts) weights, zero where not chosen."""
    k = int(cfg["num_experts_per_tok"])
    scores = jax.nn.sigmoid(dot.einsum("nd,de->ne", x, p["kernel"]))
    order = scores + p["bias"]
    chosen = jnp.zeros(scores.shape, bool)
    for _ in range(k):      # the k largest, the lower index first on a tie
        best = jnp.argmax(jnp.where(chosen, -jnp.inf, order), axis=-1)
        chosen = chosen | jax.nn.one_hot(best, scores.shape[-1], dtype=bool)
    weights = jnp.where(chosen, scores, 0.0)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights * float(cfg["routed_scaling_factor"])


def expert_ffn(dot, cfg, x, p):
    """(N, d) -> the held experts' part plus the shared expert."""
    weights = _route(dot, cfg, x, p["router"])
    first = int(cfg.get("first_expert_held", 0))
    held = weights[:, first:first + int(cfg["n_routed_experts"])]

    def add_one(y, expert_and_weight):      # one held expert after the other
        expert, w = expert_and_weight
        return y + w[:, None] * _mlp(dot, x, expert), None

    y, _ = jax.lax.scan(add_one, _mlp(dot, x, p["shared"]),
                        (p["experts"], held.T))
    return y


def _layer(dot, cfg, x, p, moe):
    eps = float(cfg["rms_norm_eps"])
    p = _as_held(cfg, p)
    h = x + _attention(dot, cfg, _rms_norm(x, p["input_norm"], eps), p["attn"])
    hn = _rms_norm(h, p["post_norm"], eps)
    if not moe:
        return h + _mlp(dot, hn, p["mlp"])
    b, t, d = hn.shape
    return h + expert_ffn(dot, cfg, hn.reshape(b * t, d), p).reshape(b, t, d)


def logits(dot, cfg, params, tokens):
    """(B, T) token ids -> (B, T, V) float32 next-token logits."""
    x = _as_held(cfg, {"embed": params["embed"][tokens]})["embed"]
    for name, moe in (("dense_layers", False), ("moe_layers", True)):
        x, _ = jax.lax.scan(
            lambda x, p, moe=moe: (_layer(dot, cfg, x, p, moe), None),
            x, params[name])
    x = _rms_norm(x, _as_held(cfg, params["final_norm"]),
                  float(cfg["rms_norm_eps"]))
    return dot.einsum("btd,dv->btv", x,
                      _as_held(cfg, params["head"])["kernel"])
