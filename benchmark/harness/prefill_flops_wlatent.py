"""The model's operations in one prefill chunk of a latent-attention decoder
with two kinds of layer (``model_type`` ``dots3_note``): what
``prefill_wlatent_mfu_pct.serve`` divides by the peak and by the chunks'
device time.

Conventions as ``harness/flops.py``: a multiply-add is two operations and
only matrix multiplications the model requires count.  For the chunk's
positions ``offset .. offset + tokens - 1``:

* each layer's attention projections at its kind's sizes (``q_a``, ``q_b``,
  ``kv_a``, ``kv_b`` once a token, the gate's ``W_g``, ``o``);
* attention at the kind's head sizes over the keys the model reads: on a
  full layer position ``t`` reads ``min(t + 1, index_topk)``, on a window
  layer ``min(t + 1, sliding_window_size)``; scores over ``qk_nope_head_dim
  + qk_rope_head_dim`` and values over ``v_head_dim``, every head (an
  implementation that attends under a mask computes more, one that absorbs
  ``kv_b`` other products: neither counts);
* on a full layer the indexer's projections and its scores over the ``t +
  1`` keys a selection has to see;
* a dense layer's MLP; a sparse layer's router, its shared expert, and of
  the routed experts each assignment to a held expert once
  (``num_experts_per_tok`` a token times ``assignments_here_share``, by the
  device's count);
* and once a chunk the head, at its last position.

``shape`` is a configuration file's ``shape`` group.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.harness.decode_bytes_wlatent import (
    FULL, SLIDING, kind_sizes, layer_counts)
from benchmark.harness.prefill_flops_dsa import _sum_min


def _projections(shape, layer_type) -> int:
    s, d = kind_sizes(shape, layer_type), int(shape["hidden_size"])
    h, nope, rot = (s["num_attention_heads"], s["qk_nope_head_dim"],
                    s["qk_rope_head_dim"])
    return (d * s["q_lora_rank"] + s["q_lora_rank"] * h * (nope + rot)
            + d * (s["kv_lora_rank"] + rot)
            + s["kv_lora_rank"] * h * (nope + s["v_head_dim"])
            + d * h + h * s["v_head_dim"] * d)


def _per_key(shape, layer_type) -> int:
    s = kind_sizes(shape, layer_type)
    return s["num_attention_heads"] * (
        s["qk_nope_head_dim"] + s["qk_rope_head_dim"] + s["v_head_dim"])


def prefill_chunk_flops(shape: Dict[str, Any], *, offset: int, tokens: int,
                        assignments_here_share: float) -> Dict[str, float]:
    """Operations of one chunk, by part and in all."""
    if offset < 0 or tokens < 1:
        raise ValueError(f"a chunk of {tokens} positions at {offset}")
    if not 0.0 <= assignments_here_share <= 1.0:
        raise ValueError(
            f"assignments_here_share {assignments_here_share} outside 0..1")
    d = int(shape["hidden_size"])
    hi, di = int(shape["index_n_heads"]), int(shape["index_head_dim"])
    n = layer_counts(shape)
    selected = _sum_min(offset, tokens, int(shape["index_topk"]))
    in_window = _sum_min(offset, tokens, int(shape["sliding_window_size"]))
    seen = _sum_min(offset, tokens, offset + tokens)
    expert = 3 * d * int(shape["moe_intermediate_size"])
    macs = {
        "projections_full": n["full"] * tokens * _projections(shape, FULL),
        "projections_window": n["window"] * tokens * _projections(
            shape, SLIDING),
        "attention_full": n["full"] * selected * _per_key(shape, FULL),
        "attention_window": n["window"] * in_window * _per_key(
            shape, SLIDING),
        "indexer_projections": n["full"] * tokens * (
            int(shape["q_lora_rank"]) * hi * di + d * di + d * hi),
        "index_scores": n["full"] * seen * hi * di,
        "dense_mlp": n["dense"] * tokens * 3 * d * int(
            shape["intermediate_size"]),
        "router": n["sparse"] * tokens * d * int(shape["router_width"]),
        "shared_experts": n["sparse"] * tokens * int(
            shape["n_shared_experts"]) * expert,
        "routed_experts": n["sparse"] * tokens * int(
            shape["num_experts_per_tok"]) * assignments_here_share * expert,
        "head": d * int(shape["vocab_size"]),
    }
    out = {part: 2.0 * count for part, count in macs.items()}
    out["total"] = sum(out.values())
    return out
