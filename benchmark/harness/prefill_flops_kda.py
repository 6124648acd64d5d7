"""The model's operations in one prefill chunk of a decoder of gated
delta-rule linear attention with grouped-query layers between
(``model_type`` ``solar_open2``): what ``prefill_state_mfu_pct.serve``
divides by the peak and by the chunks' device time.

Conventions as ``harness/flops.py``: a multiply-add is two operations and
only matrix multiplications the model requires count.  For the chunk's
positions ``offset .. offset + tokens - 1``:

* a GQA layer: its projections (``q``, ``k``, ``v``, gate, ``o``) and
  causal attention over the ``t + 1`` keys position ``t`` reads, scores
  and values at ``head_dim``, every query head;
* a linear layer: its projections (``qkv``, the two low-rank gates,
  ``beta``, ``o``) and the chunk-wise rule's products at sub-chunks of
  ``RULE_CHUNK`` positions, a head: with ``C`` the sub-chunk and ``D`` the
  head size, the two decayed Gram matrices ``K K^T`` and ``Q K^T`` (``2 C^2
  D``, half of each above the diagonal and still computed by a product),
  the triangular solve against ``[V | K]`` (``C^2 D`` by substitution),
  ``W S``, ``Q S`` and ``K^T U`` on the state (``3 C D^2``) and ``A U``
  (``C^2 D``): ``4 C^2 D + 3 C D^2`` a sub-chunk, ``4 C D + 3 D^2`` a
  position (the recurrence a position at a time needs ``3 D^2`` and no
  Gram matrix: the chunk-wise form is what runs on a matrix unit, so it is
  what is counted);
* every layer: the router, the shared expert, and of the routed experts
  each assignment to a held expert once (``num_experts_per_tok`` a token
  times the share of the router's choices that fall on held experts,
  ``assignments_here_share``, by the device's count);
* and once a chunk the head, at its last position.

``shape`` is a configuration file's ``shape`` group.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.harness.decode_bytes_kda import kda_width, layer_counts

RULE_CHUNK = 64


def prefill_chunk_flops(shape: Dict[str, Any], *, offset: int, tokens: int,
                        assignments_here_share: float) -> Dict[str, float]:
    """Operations of one chunk, by part and in all."""
    if offset < 0 or tokens < 1:
        raise ValueError(f"a chunk of {tokens} positions at {offset}")
    if not 0.0 <= assignments_here_share <= 1.0:
        raise ValueError(
            f"assignments_here_share {assignments_here_share} outside 0..1")
    d, hd = int(shape["hidden_size"]), int(shape["head_dim"])
    heads = int(shape["num_attention_heads"])
    wide, narrow = heads * hd, int(shape["num_key_value_heads"]) * hd
    f, r = kda_width(shape), int(shape["kda_gate_rank"])
    hk, dk = int(shape["kda_num_heads"]), int(shape["kda_head_dim"])
    n = layer_counts(shape)
    seen = tokens * (2 * offset + tokens + 1) // 2     # sum of t + 1
    expert = 3 * d * int(shape["moe_intermediate_size"])
    macs = {
        "gqa_projections": n["gqa"] * tokens * (3 * d * wide + 2 * d * narrow),
        "gqa_attention": n["gqa"] * seen * heads * 2 * hd,
        "kda_projections": n["kda"] * tokens * (
            3 * d * f + 2 * (d * r + r * f) + d * hk + f * d),
        "kda_rule": n["kda"] * tokens * hk * (
            4 * RULE_CHUNK * dk + 3 * dk * dk),
        "router": n["layers"] * tokens * d * int(shape["router_width"]),
        "shared_experts": n["layers"] * tokens * int(
            shape["n_shared_experts"]) * expert,
        "routed_experts": n["layers"] * tokens * int(
            shape["num_experts_per_tok"]) * assignments_here_share * expert,
        "head": d * int(shape["vocab_size"]),
    }
    out = {part: 2.0 * count for part, count in macs.items()}
    out["total"] = sum(out.values())
    return out
