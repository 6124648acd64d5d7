"""The model's operations in one prefill chunk of a latent-attention
decoder with a learned indexer (``model_type`` ``glm_moe_dsa``): what
``prefill_mfu_pct.serve`` divides by the peak and by the chunks' device
time.

Conventions as ``harness/flops.py``: a multiply-add is two operations and
only matrix multiplications the model requires count.  For the chunk's
positions ``offset .. offset + tokens - 1``, per layer:

* the attention's projections (``q_a``, ``q_b``, ``kv_a``, ``kv_b`` once a
  token, ``o``);
* attention at the published head sizes over the keys the model reads:
  position ``t`` reads ``min(t + 1, index_topk)``, scores over
  ``qk_nope_head_dim + qk_rope_head_dim`` and values over ``v_head_dim``,
  every head (an implementation that attends under a mask computes more,
  one that absorbs ``kv_b`` other products: neither counts);
* on a ``full`` layer the indexer's projections and its scores over the
  ``t + 1`` keys a selection has to see;
* a dense layer's MLP; a sparse layer's router, its shared expert, and of
  the routed experts each assignment to a held expert once
  (``num_experts_per_tok`` a token times the share of the router's
  choices that fall on held experts, ``assignments_here_share``, by the
  device's count): not every held expert over every position;
* and once a chunk the head, at its last position.

``shape`` is a configuration file's ``shape`` group.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.harness.decode_bytes_dsa import layer_counts


def _sum_min(offset: int, tokens: int, cap: int) -> int:
    """Sum over ``t`` in ``offset .. offset + tokens - 1`` of ``min(t + 1,
    cap)``."""
    end = offset + tokens
    rising = max(0, min(end, cap) - offset)      # positions with t + 1 <= cap
    first, last = offset + 1, offset + rising
    return rising * (first + last) // 2 + (tokens - rising) * cap


def prefill_chunk_flops(shape: Dict[str, Any], *, offset: int, tokens: int,
                        assignments_here_share: float) -> Dict[str, float]:
    """Operations of one chunk, by part and in all."""
    if offset < 0 or tokens < 1:
        raise ValueError(f"a chunk of {tokens} positions at {offset}")
    if not 0.0 <= assignments_here_share <= 1.0:
        raise ValueError(
            f"assignments_here_share {assignments_here_share} outside 0..1")
    d, h = int(shape["hidden_size"]), int(shape["num_attention_heads"])
    nope, rot = int(shape["qk_nope_head_dim"]), int(shape["qk_rope_head_dim"])
    vd = int(shape["v_head_dim"])
    q_rank, kv_rank = int(shape["q_lora_rank"]), int(shape["kv_lora_rank"])
    hi, di = int(shape["index_n_heads"]), int(shape["index_head_dim"])
    n = layer_counts(shape)
    projections = (d * q_rank + q_rank * h * (nope + rot)
                   + d * (kv_rank + rot) + kv_rank * h * (nope + vd)
                   + h * vd * d)
    read = _sum_min(offset, tokens, int(shape["index_topk"]))
    seen = _sum_min(offset, tokens, offset + tokens)
    expert = 3 * d * int(shape["moe_intermediate_size"])
    macs = {
        "projections": n["layers"] * tokens * projections,
        "attention": n["layers"] * read * h * (nope + rot + vd),
        "indexer_projections": n["full"] * tokens * (
            q_rank * hi * di + d * di + d * hi),
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
