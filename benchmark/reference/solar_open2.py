"""A decoder of gated delta-rule linear attention (Kimi Delta Attention,
arXiv:2510.26692) with one grouped-query softmax layer in four, every MLP a
layer of sparse experts (``model_type`` ``solar_open2``;
Solar-Open2-250B's ``config.json``), the plain reference.

Written from the configuration's keys; every matrix product goes through
``dot.einsum``; no cache, no chunk-wise form, no kernel, no batching of
requests beyond the rows it is given.  It imports nothing from the package.

    h = x + Mixer_l(RMSNorm(x));  y = h + MoE(RMSNorm(h));  eps
    ``rms_norm_eps``; every layer an expert layer (``first_k_dense_replace``
    0); no positional term anywhere (``use_rope`` false); final RMSNorm;
    untied head.

GQA mixer, where ``l`` is in ``gqa_layers``: ``q = x W_q``
(``num_attention_heads`` heads of ``head_dim``), ``k = x W_k``, ``v = x W_v``
(``num_key_value_heads`` heads, each serving ``G`` query heads in turn: the
K/V heads are repeated here); causal softmax of ``q . k / sqrt(head_dim)``,
no rotation, no norm; ``y = (o * sigmoid(x W_g)) W_o``.  The ``(T, T)``
scores are taken a block of queries at a time (``QUERY_BLOCK``) so that a
row of some thousand positions fits; every query sees every key before it.

KDA mixer, everywhere else, ``H`` = ``linear_attn_config.num_heads`` heads
of ``D`` = ``linear_attn_config.head_dim``: ``[q~ | k~ | v~] = x W_qkv``;
each channel through a causal convolution over its own last
``short_conv_kernel_size`` inputs (tap ``j`` of ``conv`` on the input
``kernel - 1 - j`` back, zeros before the row) and SiLU; ``q``, ``k``
divided by ``sqrt(|.|^2 + 1e-6)`` a head, ``q`` times ``D^-1/2``.
``alpha_t = exp(-exp(A_h) softplus(W_a2 (W_a1 x_t) + b_dt))`` a channel,
``beta_t = 2 sigmoid(x_t W_b)`` a head.  A ``lax.scan`` over the positions
of

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

from ``S = 0``, then ``y = W_o (RMSNorm_head(o_t) * sigmoid(W_g2 (W_g1
x_t)))`` with one ``D``-wide scale for every head.

MoE: ``s = sigmoid(x W_r)``; the ``num_experts_per_tok`` largest of ``s +
b`` chosen (``b`` the correction bias, for the choice alone; the lower index
first on a tie); ``w = s[chosen] / sum(s[chosen]) *
routed_scaling_factor``; ``y = sum_e w_e E_e(x) + E_shared(x)``, ``E(x) =
W_d(silu(W_g x) * W_u x)`` at ``moe_intermediate_size``.

**The share.**  ``cfg["n_routed_experts"]`` experts are held, numbers
``first_expert_held ..``, of the ``n_routed_experts_published`` the router
scores.  The sum runs over the held experts alone (one after the other,
each over every token with its weight zero where the router chose
otherwise); what the absent experts would add is left out, as on the chip
that holds this share.

Weights, in the tree of the benchmark's draw: ``embed`` (V, d); ``head``
(d, V); ``final_norm``; ``layers`` (every layer, stacked): ``input_norm``,
``post_norm``, ``router`` (``kernel``, ``bias``), ``shared``, ``experts``
(``gate_up`` (held, 2 x width, d): W_g's rows, then W_u's; ``down`` (held,
width, d)); ``gqa`` (the GQA layers in order, stacked): ``q``, ``k``, ``v``,
``gate``, ``o``; ``kda`` (the others in order, stacked): ``qkv``, ``conv``
(``scale`` (kernel, 3 H D)), ``a_down``, ``a_up``, ``beta``, ``g_down``,
``g_up``, ``o_norm``, ``o``; ``kda_decay`` (the same layers): ``A_log`` (H),
``dt_bias`` (H D).

Departures, which the configuration file lists under ``assumed``: the
parameters are the draw rounded to ``parameter_dtype`` (the arithmetic here
is float32 on those values; ``A_log``, ``dt_bias`` and the router's bias are
float32 leaves and stay as drawn); the GQA gate elementwise and from the
layer's input; both low-rank gates of rank ``D``; the router sigmoid with a
correction bias and the experts SiLU-gated; no multi-token head.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


def _as_held(cfg, tree):
    """The draw as the model holds it: rounded to the parameter type's
    values, in float32 (no array of the narrow type: see
    ``reference/glm4_moe_lite.py``); the router's bias as drawn, as are
    the ``kda_decay`` leaves, which never come here."""
    info = jnp.finfo(jnp.dtype(cfg["parameter_dtype"]))

    def one(path, x):
        if [getattr(k, "key", None) for k in path][-2:] == ["router", "bias"]:
            return x
        return jax.lax.reduce_precision(x, info.nexp, info.nmant)

    return jax.tree_util.tree_map_with_path(one, tree)


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def _mlp(dot, x, p):
    """``gate_up`` holds W_g's rows and then W_u's, each (width, d)."""
    g, u = jnp.split(
        dot.einsum("...d,gd->...g", x, p["gate_up"]["kernel"]), 2, axis=-1)
    return dot.einsum("...f,fd->...d", jax.nn.silu(g) * u, p["down"]["kernel"])


def _gqa(dot, cfg, x, p):
    b, t, _ = x.shape
    h, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    dim = int(cfg["head_dim"])
    q = dot.einsum("btd,df->btf", x, p["q"]["kernel"]).reshape(b, t, h, dim)
    k = dot.einsum("btd,df->btf", x, p["k"]["kernel"]).reshape(b, t, hkv, dim)
    v = dot.einsum("btd,df->btf", x, p["v"]["kernel"]).reshape(b, t, hkv, dim)
    k, v = (jnp.repeat(a, h // hkv, axis=2) for a in (k, v))
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def some_queries(args):
        qb, first = args                                   # (B, block, H, D)
        scores = dot.einsum("bqhd,bkhd->bhqk", qb, k) / np.sqrt(dim)
        ok = (jnp.arange(t)[None, :]
              <= first + jnp.arange(block)[:, None])
        probs = jax.nn.softmax(jnp.where(ok[None, None], scores, -1e30),
                               axis=-1)
        return dot.einsum("bhqk,bkhd->bqhd", probs, v)

    ctx = jax.lax.map(some_queries, (
        jnp.moveaxis(q.reshape(b, t // block, block, h, dim), 1, 0),
        jnp.arange(t // block) * block))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, t, h * dim)
    gate = jax.nn.sigmoid(dot.einsum("btd,df->btf", x, p["gate"]["kernel"]))
    return dot.einsum("btf,fd->btd", ctx * gate, p["o"]["kernel"])


def kda_recurrence(dot, q, k, v, alpha, beta, state=None):
    """The delta rule a position at a time: ``q``, ``k``, ``v``, ``alpha``
    ``(B, T, H, D)``, ``beta`` ``(B, T, H)`` -> ``o`` ``(B, T, H, D)`` and
    the state after the last position ``(B, H, D, D)`` (key channel, then
    value channel), from ``state`` (zeros where None)."""
    b, _, h, d = q.shape
    if state is None:
        state = jnp.zeros((b, h, d, d), jnp.float32)

    def one_position(s, xs):
        q_t, k_t, v_t, a_t, b_t = xs                # (B, H, D) ..., (B, H)
        s = a_t[..., None] * s                      # Diag(alpha) S
        seen = dot.einsum("bhk,bhkv->bhv", k_t, s)  # k^T Diag(alpha) S
        s = s + b_t[..., None, None] * (
            k_t[..., None] * (v_t - seen)[..., None, :])
        return s, dot.einsum("bhk,bhkv->bhv", q_t, s)

    over_t = lambda a: jnp.moveaxis(a, 1, 0)
    state, out = jax.lax.scan(
        one_position, state, tuple(over_t(a) for a in (q, k, v, alpha, beta)))
    return jnp.moveaxis(out, 0, 1), state


def kda_inputs(dot, cfg, x, p, decay):
    """-> ``q``, ``k``, ``v``, ``alpha`` ``(B, T, H, D)``, ``beta`` ``(B, T,
    H)`` and the output gate ``(B, T, H D)``."""
    b, t, _ = x.shape
    group = cfg["linear_attn_config"]
    h, dim = int(group["num_heads"]), int(group["head_dim"])
    taps = int(group["short_conv_kernel_size"])
    pre = dot.einsum("btd,df->btf", x, p["qkv"]["kernel"])
    run = jnp.pad(pre, ((0, 0), (taps - 1, 0), (0, 0)))
    mixed = sum(p["conv"]["scale"][j] * run[:, j:j + t] for j in range(taps))
    q, k, v = (a.reshape(b, t, h, dim)
               for a in jnp.split(jax.nn.silu(mixed), 3, axis=-1))
    unit = lambda a: a / jnp.sqrt(
        jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) / np.sqrt(dim), unit(k)
    low = lambda down, up: dot.einsum(
        "btr,rf->btf", dot.einsum("btd,dr->btr", x, p[down]["kernel"]),
        p[up]["kernel"])
    alpha = jnp.exp(-jnp.exp(decay["A_log"])[:, None] * jax.nn.softplus(
        low("a_down", "a_up") + decay["dt_bias"]).reshape(b, t, h, dim))
    beta = jax.nn.sigmoid(dot.einsum("btd,dh->bth", x, p["beta"]["kernel"]))
    if cfg["kda_allow_neg_eigval"]:
        beta = 2.0 * beta
    return q, k, v, alpha, beta, jax.nn.sigmoid(low("g_down", "g_up"))


def _kda(dot, cfg, x, p, decay):
    b, t, _ = x.shape
    q, k, v, alpha, beta, gate = kda_inputs(dot, cfg, x, p, decay)
    out, _ = kda_recurrence(dot, q, k, v, alpha, beta)
    out = _rms_norm(out, p["o_norm"], float(cfg["rms_norm_eps"]))
    return dot.einsum("btf,fd->btd", out.reshape(b, t, -1) * gate,
                      p["o"]["kernel"])


def _route(dot, cfg, x, p):
    """-> (N, published experts) weights, zero where not chosen."""
    scores = jax.nn.sigmoid(dot.einsum("nd,de->ne", x, p["kernel"]))
    order = scores + p["bias"]
    chosen = jnp.zeros(scores.shape, bool)
    for _ in range(int(cfg["num_experts_per_tok"])):
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


def _layer(dot, cfg, x, common, mixer, decay):
    """``decay`` is the layer's ``kda_decay`` leaves, None on a GQA layer."""
    eps = float(cfg["rms_norm_eps"])
    p = _as_held(cfg, common)
    xn = _rms_norm(x, p["input_norm"], eps)
    if decay is None:
        h = x + _gqa(dot, cfg, xn, _as_held(cfg, mixer))
    else:
        h = x + _kda(dot, cfg, xn, _as_held(cfg, mixer), decay)
    hn = _rms_norm(h, p["post_norm"], eps)
    b, t, d = hn.shape
    return h + expert_ffn(dot, cfg, hn.reshape(b * t, d), p).reshape(b, t, d)


def layer_kinds(cfg):
    """Layer by layer, True where the mixer is GQA: the published
    ``gqa_layers`` below ``num_hidden_layers`` (a cut in depth keeps whole
    periods)."""
    named = set(int(l) for l in cfg["gqa_layers"])
    return [l in named for l in range(int(cfg["num_hidden_layers"]))]


def logits(dot, cfg, params, tokens):
    """(B, T) token ids -> (B, T, V) float32 next-token logits."""
    x = _as_held(cfg, {"embed": params["embed"][tokens]})["embed"]
    seen = {True: 0, False: 0}
    for l, is_gqa in enumerate(layer_kinds(cfg)):
        at = lambda tree, i: jax.tree.map(lambda w: w[i], tree)
        i = seen[is_gqa]
        x = _layer(dot, cfg, x, at(params["layers"], l),
                   at(params["gqa" if is_gqa else "kda"], i),
                   None if is_gqa else at(params["kda_decay"], i))
        seen[is_gqa] += 1
    x = _rms_norm(x, _as_held(cfg, params["final_norm"]),
                  float(cfg["rms_norm_eps"]))
    return dot.einsum("btd,dv->btv", x,
                      _as_held(cfg, params["head"])["kernel"])
