"""A latent-attention, sparse-expert decoder with two kinds of layer, each
with its own sizes: full layers that read what a learned indexer selects,
window layers that read a sliding window (``model_type`` ``dots3_note``;
dots3-note-prev's ``config.json``), the plain reference.

Written from the configuration's keys; every product goes through
``dot.einsum``; no cache, no kernel, no batching of requests beyond the rows
it is given.  It imports nothing from the package.  The indexer's scores,
the selection, the router and the expert sum are
``benchmark/reference/glm_moe_dsa.py``'s, handed one kind's keys.

    h = x + Attn_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h));  final RMSNorm;
    untied head.  rms_norm_eps 1e-5;  d = hidden_size.

``layer_types[l]`` is ``full_attention`` or ``sliding_attention``.  Sizes
by kind (the window layers' keys carry the prefix ``swa_``):

    kind     heads H  r_q   r_kv  d_n  d_r  d_v  theta  reads
    full     128      1024  512   128  64   128  8e7    S_t (the indexer's)
    sliding  64       1024  1024  192  64   128  5e4    s <= t, t - s < 513

Attention, per layer, ``xn = RMSNorm(x)``, query position ``t``:

- Latent attention, either kind: ``c_q = a_q * RMSNorm(xn W_qa)``, ``a_q =
  sqrt(d / r_q)``; ``q = c_q W_qb`` -> ``H`` heads of ``[q_n (d_n) | q_r
  (d_r)]``, ``q_r = RoPE_theta(q_r)``.  ``[c | k_r] = xn W_kva``; ``c_kv =
  a_kv * RMSNorm(c)``, ``a_kv = sqrt(d / r_kv)``; ``k_r = RoPE_theta(k_r)``,
  one for all heads.  ``[k_n | v] = c_kv W_kvb`` a head.  Scores ``(q_n .
  k_n + q_r . k_r) / sqrt(d_n + d_r)``, softmax over the positions the kind
  reads, ``o_h = sum p v``.
- The gate: ``g = sigmoid(xn W_g)``, ``W_g`` ``(d, H)``, one scalar a head
  and position; the layer's output is ``concat_h(g_h * o_h) W_o``.
- The indexer, on every full layer and on no other (no layer shares
  another's): from this layer's scaled ``c_q`` and ``xn``, ``index_n_heads``
  heads of ``index_head_dim``, the first ``qk_rope_head_dim`` rotated by the
  full kind's ``theta``; ``k^I = LayerNorm(xn W^I_k)``; ``I[t, s] = sum_h
  w[t, h] relu(q^I[t, h] . k^I[s])``; ``S_t`` the ``min(t + 1, index_topk)``
  positions of largest score, the lower position first on a tie.
- A window layer reads ``s <= t`` with ``t - s < sliding_window_size``: the
  window counts the query.

FFN: the first ``first_k_dense_replace`` layers a gated MLP ``W_d(silu(W_g
x) * W_u x)`` of ``intermediate_size``; the others sigmoid scores, the
``num_experts_per_tok`` largest of score + correction bias chosen, weights
the chosen scores over their sum times ``routed_scaling_factor``, plus one
shared expert; each expert the gated MLP at ``moe_intermediate_size``.

**The share.**  ``cfg["n_routed_experts"]`` experts are held, numbers
``first_expert_held ..``, of the ``n_routed_experts_published`` the router
scores; the sum runs over the held experts alone and the shared expert
whole, as on the chip that holds this share.

Queries are taken in blocks of ``QUERY_BLOCK`` so that a row of 8,192
positions fits beside the float32 weights (a block's scores are (128 heads,
128, 8,192) x 4 B = 537 MB).

Weights, in the tree of the benchmark's draw: ``embed`` (V, d); ``head``
(d, V); ``final_norm``; ``layer_0`` ... ``layer_{n-1}``, each
``input_norm``, ``post_norm``, ``attn`` (``q_a``, ``q_a_norm``, ``q_b``,
``kv_a``, ``kv_a_norm``, ``kv_b``, ``o``, ``gate``), on a full layer
``indexer`` (``wq_b``, ``wk``, ``k_norm``, ``weights_proj``), and ``mlp``
(dense) or ``router`` (``kernel``, ``bias``), ``shared``, ``experts``.

Departures, which the configuration file lists under ``assumed``: the
gate's form and the rescale's (the config names the switches, not the
maps); the parameters are the draw rounded to ``parameter_dtype`` (the
arithmetic here is float32 on those values), the correction bias stays
float32; rotary pairs are (i, i + half); no YaRN factor (``rope_scaling``
null); index keys are not rounded to float8 and no Hadamard rotation is
applied; no vision or audio tower and no multi-token-prediction layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.glm_moe_dsa import (
    _by_query_blocks, _held, _mlp, _rms_norm, _rope, expert_ffn,
    index_scores, selected)

FULL, SLIDING = "full_attention", "sliding_attention"
_SIZES = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
          "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta")


def kind_config(cfg, layer_type):
    """The configuration with one kind's sizes under the plain keys."""
    if layer_type == FULL:
        return cfg
    return {**cfg, **{key: cfg[f"swa_{key}"] for key in _SIZES}}


def window_mask(cfg, t):
    """(1, T, T): True where a window layer's query may read the key."""
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    return ((j <= i) & (i - j < int(cfg["sliding_window_size"])))[None]


def _attention(dot, cfg, xn, p, layer_type):
    """-> the layer's attention output and the mask it read; ``cfg`` holds
    the kind's sizes under the plain keys."""
    b, t, d = xn.shape
    h = int(cfg["num_attention_heads"])
    nope, rot = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    vd, rank = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    theta = float(cfg["rope_theta"])
    rescale = bool(cfg["apply_mla_qkv_lora_rescale"])
    a_q = np.sqrt(d / int(cfg["q_lora_rank"])) if rescale else 1.0
    a_kv = np.sqrt(d / rank) if rescale else 1.0
    w_of = lambda name: _held(cfg, p["attn"][name]["kernel"])
    c_q = a_q * _rms_norm(cfg, dot.einsum("btd,dr->btr", xn, w_of("q_a")),
                          p["attn"]["q_a_norm"])
    if layer_type == FULL:
        mask = selected(cfg, index_scores(dot, cfg, xn, c_q, p["indexer"]))
    else:
        mask = jnp.broadcast_to(window_mask(cfg, t), (b, t, t))
    w_q = w_of("q_b").reshape(-1, h, nope + rot)
    q_n = dot.einsum("btr,rhd->bthd", c_q, w_q[..., :nope])
    q_r = _rope(dot.einsum("btr,rhd->bthd", c_q, w_q[..., nope:]), theta)
    kva = dot.einsum("btd,dc->btc", xn, w_of("kv_a"))
    c_kv = a_kv * _rms_norm(cfg, kva[..., :rank], p["attn"]["kv_a_norm"])
    k_r = _rope(kva[..., rank:], theta)         # (B, T, rot), for all heads
    w_kv = w_of("kv_b").reshape(rank, h, nope + vd)
    k_n = dot.einsum("btc,chd->bthd", c_kv, w_kv[..., :nope])
    v = dot.einsum("btc,chd->bthd", c_kv, w_kv[..., nope:])

    def block(args):
        qn_b, qr_b, mask_b = args       # (B, Q, H, .), (B, Q, H, .), (B, Q, T)
        scores = (dot.einsum("bqhd,bkhd->bhqk", qn_b, k_n)
                  + dot.einsum("bqhd,bkd->bhqk", qr_b, k_r)
                  ) / np.sqrt(nope + rot)
        probs = jax.nn.softmax(
            jnp.where(mask_b[:, None], scores, -1e30), axis=-1)
        return dot.einsum("bhqk,bkhd->bqhd", probs, v)

    ctx = _by_query_blocks(block, (q_n, q_r, mask), t)      # (B, T, H, vd)
    if cfg.get("attention_gate_type") == "headwise":
        gate = jax.nn.sigmoid(dot.einsum("btd,dh->bth", xn, w_of("gate")))
        ctx = gate[..., None] * ctx
    return dot.einsum("btf,fd->btd", ctx.reshape(b, t, h * vd),
                      w_of("o")), mask


def layer_types(cfg):
    """The kinds of the layers that are run: the list's first
    ``num_hidden_layers``."""
    return list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]


def _layer(dot, cfg, x, p, l, layer_type):
    kind = kind_config(cfg, layer_type)
    if layer_type == SLIDING:
        kind["attention_gate_type"] = cfg.get("swa_attention_gate_type")
    out, mask = _attention(dot, kind, _rms_norm(cfg, x, p["input_norm"]), p,
                           layer_type)
    h = x + out
    hn = _rms_norm(cfg, h, p["post_norm"])
    if l < int(cfg["first_k_dense_replace"]):
        return h + _mlp(dot, cfg, hn, p["mlp"]), mask
    b, t, d = hn.shape
    return h + expert_ffn(dot, cfg, hn.reshape(b * t, d), p).reshape(
        b, t, d), mask


def logits(dot, cfg, params, tokens, selections=None):
    """(B, T) token ids -> (B, T, V) float32 next-token logits.  A list
    given as ``selections`` receives each layer's mask (B, T, T): a full
    layer's selection, a window layer's window."""
    x = _held(cfg, params["embed"][tokens])
    for l, layer_type in enumerate(layer_types(cfg)):
        x, mask = _layer(dot, cfg, x, params[f"layer_{l}"], l, layer_type)
        if selections is not None:
            selections.append(mask)
    x = _rms_norm(cfg, x, params["final_norm"])
    return dot.einsum("btd,dv->btv", x, _held(cfg, params["head"]["kernel"]))
