"""A latent-attention, sparse-expert decoder with learned sparse attention
(``model_type`` ``glm_moe_dsa``; GLM-5.2's ``config.json``), the plain
reference.

Written from the configuration's keys; every product goes through
``dot.einsum``; no cache, no kernel, no batching of requests beyond the rows
it is given.  It imports nothing from the package.

    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h));  final RMSNorm;
    untied head.

Attention, per layer ``l``, ``xn = RMSNorm(x)``, query position ``t``, key
position ``s <= t``:

- MLA: ``c_q = RMSNorm(xn W_qa)``; ``q = c_q W_qb`` -> heads of
  (``qk_nope_head_dim`` | ``qk_rope_head_dim``); ``[c_kv | k_r] = xn W_kva``;
  ``c_kv = RMSNorm(c_kv)``; ``k_r = RoPE(k_r)``, one for all heads; ``[k_n |
  v] = c_kv W_kvb`` -> heads of (``qk_nope_head_dim`` | ``v_head_dim``); ``q =
  [q_n | RoPE(q_r)]``, ``k = [k_n | k_r]``; scores ``q . k /
  sqrt(qk_nope_head_dim + qk_rope_head_dim)``.
- The indexer, where ``indexer_types[l]`` is ``full``: ``q^I = c_q W^I_q`` ->
  ``index_n_heads`` heads of ``index_head_dim``, the first
  ``qk_rope_head_dim`` of each rotated; ``k^I = LayerNorm(xn W^I_k)`` (scale
  and offset, eps ``index_norm_eps``), rotated the same way; ``w = xn W^I_w
  * index_n_heads^-1/2 * index_head_dim^-1/2``; ``I[t, s] = sum_h w[t, h]
  relu(q^I[t, h] . k^I[s])``.  ``S_t`` is the ``min(t + 1, index_topk)``
  positions ``s <= t`` of largest ``I[t, s]``, the lower position first on a
  tie (``lax.top_k`` over the whole row of scores, -inf where ``s > t``).
- Where it is ``shared``: no indexer; ``S_t`` is that of the nearest ``full``
  layer before it.
- Softmax over ``s in S_t`` alone (a mask), values likewise, then ``W_o``.

Keys and values are expanded in full and the whole ``I`` matrix is written
out; queries are taken in blocks of ``QUERY_BLOCK`` (``lax.map``) so that a
row of 8,192 positions fits beside the float32 weights: the block's scores
are (heads, block, T) and its index scores (index heads, block, T).

FFN: where ``mlp_layer_types[l]`` is ``dense`` a gated MLP ``W_d(silu(W_g x)
* W_u x)`` of ``intermediate_size``; where ``sparse``, ``s = sigmoid(x
W_r)``; the ``num_experts_per_tok`` largest of ``s + b`` chosen (``b`` the
correction bias, used for the choice alone); ``w = s[chosen] /
sum(s[chosen]) * routed_scaling_factor``; ``y = sum_e w_e E_e(x) +
E_shared(x)``, each expert the gated MLP at ``moe_intermediate_size``.

**The share.**  ``cfg["n_routed_experts"]`` experts are held, numbers
``first_expert_held ..``, of the ``n_routed_experts_published`` the router
scores.  The sum runs over the held experts alone (one after the other,
each over every token with its weight zero where the router chose
otherwise); what the absent experts would add is left out, as on the chip
that holds this share.

Weights, in the tree of the benchmark's draw: ``embed`` (V, d); ``head``
(d, V); ``final_norm``; ``layer_0`` ... ``layer_{n-1}``, each
``input_norm``, ``post_norm``, ``attn`` (``q_a``, ``q_a_norm``, ``q_b``,
``kv_a``, ``kv_a_norm``, ``kv_b``, ``o``), on a ``full`` layer ``indexer``
(``wq_b``, ``wk``, ``k_norm`` (``scale``, ``bias``), ``weights_proj``), and
``mlp`` (dense) or ``router`` (``kernel``, ``bias``), ``shared``,
``experts`` (``gate_up`` (held, 2 x width, d): W_g's rows, then W_u's;
``down`` (held, width, d)).

Departures, which the configuration file lists: the parameters are the draw
rounded to ``parameter_dtype`` (the arithmetic here is float32 on those
values), the correction bias stays float32; rotary pairs are (i, i + half);
index keys are not rounded to float8 and no Hadamard rotation is applied
(an orthogonal map on both sides leaves ``q^I . k^I`` as it is); no
multi-token-prediction layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128


def _held(cfg, x):
    """A drawn leaf as the model holds it: rounded to the parameter type's
    values, in float32, without an array of the narrow type.  Taken where a
    leaf is used, one leaf at a time: rounded copies of a whole layer's
    leaves at once are 2 GB at the cell's size."""
    info = jnp.finfo(jnp.dtype(cfg["parameter_dtype"]))
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _rms_norm(cfg, x, p):
    eps = float(cfg["rms_norm_eps"])
    return x * jax.lax.rsqrt(jnp.mean(
        jnp.square(x), axis=-1, keepdims=True) + eps) * _held(cfg, p["scale"])


def _layer_norm(cfg, x, p):
    eps = float(cfg["index_norm_eps"])
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * _held(cfg, p["scale"])
            + _held(cfg, p["bias"]))


def _rope(x, theta, dims=None):
    """(B, T, ..., D) with position t at axis 1; the first ``dims`` values
    (all by default) rotated, pairs (i, i + dims / 2)."""
    dims = x.shape[-1] if dims is None else dims
    t, half = x.shape[1], dims // 2
    freq = theta ** (-np.arange(half, dtype=np.float32) / half)
    angle = np.arange(t, dtype=np.float32)[:, None] * freq[None, :]
    angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (half,))
    cos, sin = np.cos(angle), np.sin(angle)
    a, b = x[..., :half], x[..., half:dims]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., dims:]], axis=-1)


def _mlp(dot, cfg, x, p):
    """``gate_up`` holds W_g's rows and then W_u's, each (width, d)."""
    g, u = jnp.split(dot.einsum(
        "...d,gd->...g", x, _held(cfg, p["gate_up"]["kernel"])), 2, axis=-1)
    return dot.einsum("...f,fd->...d", jax.nn.silu(g) * u,
                      _held(cfg, p["down"]["kernel"]))


def index_scores(dot, cfg, xn, c_q, p):
    """``I`` (B, T, T), -inf where the key is after the query."""
    b, t, _ = xn.shape
    hi, di = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    rot, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    w_of = lambda name: _held(cfg, p[name]["kernel"])
    q = _rope(dot.einsum("btr,rf->btf", c_q, w_of("wq_b")).reshape(
        b, t, hi, di), theta, rot)
    k = _rope(_layer_norm(cfg, dot.einsum("btd,df->btf", xn, w_of("wk")),
                          p["k_norm"]), theta, rot)
    w = dot.einsum("btd,dh->bth", xn, w_of("weights_proj")) * (
        hi ** -0.5 * di ** -0.5)

    def block(args):                # a block of queries against every key
        q_b, w_b = args             # (B, Q, Hi, Di), (B, Q, Hi)
        s = jax.nn.relu(dot.einsum("bqhd,bkd->bqhk", q_b, k))
        return jnp.sum(s * w_b[..., None], axis=2)

    scores = _by_query_blocks(block, (q, w), t)
    return jnp.where(np.tril(np.ones((t, t), bool))[None], scores, -jnp.inf)


def selected(cfg, scores):
    """``I`` -> the mask (B, T, T) of each query's ``S_t``."""
    t = scores.shape[-1]
    causal = np.tril(np.ones((t, t), bool))[None]
    k = int(cfg["index_topk"])
    if t <= k:
        return jnp.broadcast_to(causal, scores.shape)
    _, chosen = jax.lax.top_k(scores, k)        # lower index first on a tie
    rows = jnp.arange(t)[None, :, None]
    mask = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None, None], rows, chosen].set(True)
    return mask & causal


def _by_query_blocks(fn, args, t):
    """``fn`` over blocks of ``QUERY_BLOCK`` queries (axis 1 of every
    argument), results joined along axis 1."""
    q = min(QUERY_BLOCK, t)
    pad = -t % q
    split = lambda a: jnp.moveaxis(jnp.pad(
        a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)).reshape(
        (a.shape[0], (t + pad) // q, q) + a.shape[2:]), 1, 0)
    out = jax.lax.map(fn, jax.tree.map(split, args))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape((out.shape[0], t + pad) + out.shape[3:])[:, :t]


def _attention(dot, cfg, xn, p, indexer, mask):
    """-> the layer's attention output and the selection mask it read.
    ``W_qb``'s and ``W_kvb``'s columns are taken apart before the products
    (a head's no-position part, its rotary part, its values), so that no
    array holds a head's parts side by side only to be cut again."""
    b, t, _ = xn.shape
    h = int(cfg["num_attention_heads"])
    nope, rot = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    vd, rank = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    theta = float(cfg["rope_theta"])
    w_of = lambda name: _held(cfg, p["attn"][name]["kernel"])
    c_q = _rms_norm(cfg, dot.einsum("btd,dr->btr", xn, w_of("q_a")),
                    p["attn"]["q_a_norm"])
    if indexer is not None:
        mask = selected(cfg, index_scores(dot, cfg, xn, c_q, indexer))
    w_q = w_of("q_b").reshape(-1, h, nope + rot)
    q_n = dot.einsum("btr,rhd->bthd", c_q, w_q[..., :nope])
    q_r = _rope(dot.einsum("btr,rhd->bthd", c_q, w_q[..., nope:]), theta)
    kva = dot.einsum("btd,dc->btc", xn, w_of("kv_a"))
    c_kv = _rms_norm(cfg, kva[..., :rank], p["attn"]["kv_a_norm"])
    k_r = _rope(kva[..., rank:], theta)         # (B, T, rot), for all heads
    w_kv = w_of("kv_b").reshape(rank, h, nope + vd)
    k_n = dot.einsum("btc,chd->bthd", c_kv, w_kv[..., :nope])
    v = dot.einsum("btc,chd->bthd", c_kv, w_kv[..., nope:])

    def block(args):        # q . k = q_n . k_n + q_r . k_r, the shared k_r
        qn_b, qr_b, mask_b = args       # (B, Q, H, .), (B, Q, H, .), (B, Q, T)
        scores = (dot.einsum("bqhd,bkhd->bhqk", qn_b, k_n)
                  + dot.einsum("bqhd,bkd->bhqk", qr_b, k_r)
                  ) / np.sqrt(nope + rot)
        probs = jax.nn.softmax(
            jnp.where(mask_b[:, None], scores, -1e30), axis=-1)
        return dot.einsum("bhqk,bkhd->bqhd", probs, v)

    ctx = _by_query_blocks(block, (q_n, q_r, mask), t).reshape(b, t, h * vd)
    return dot.einsum("btf,fd->btd", ctx, w_of("o")), mask


def _route(dot, cfg, x, p):
    """-> (N, published experts) weights, zero where not chosen."""
    k = int(cfg["num_experts_per_tok"])
    scores = jax.nn.sigmoid(
        dot.einsum("nd,de->ne", x, _held(cfg, p["kernel"])))
    order = scores + p["bias"]              # the bias stays float32
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
        return y + w[:, None] * _mlp(dot, cfg, x, expert), None

    y, _ = jax.lax.scan(add_one, _mlp(dot, cfg, x, p["shared"]),
                        (p["experts"], held.T))
    return y


def _layer(dot, cfg, x, p, l, mask):
    indexer = p["indexer"] if cfg["indexer_types"][l] == "full" else None
    out, mask = _attention(dot, cfg, _rms_norm(cfg, x, p["input_norm"]), p,
                           indexer, mask)
    h = x + out
    hn = _rms_norm(cfg, h, p["post_norm"])
    if cfg["mlp_layer_types"][l] == "dense":
        return h + _mlp(dot, cfg, hn, p["mlp"]), mask
    b, t, d = hn.shape
    return h + expert_ffn(dot, cfg, hn.reshape(b * t, d), p).reshape(b, t, d), mask


def logits(dot, cfg, params, tokens, selections=None):
    """(B, T) token ids -> (B, T, V) float32 next-token logits.  A list
    given as ``selections`` receives each layer's mask (B, T, T)."""
    x = _held(cfg, params["embed"][tokens])
    mask = None
    for l in range(int(cfg["num_hidden_layers"])):
        x, mask = _layer(dot, cfg, x, params[f"layer_{l}"], l, mask)
        if selections is not None:
            selections.append(mask)
    x = _rms_norm(cfg, x, params["final_norm"])
    return dot.einsum("btd,dv->btv", x, _held(cfg, params["head"]["kernel"]))
