"""The least bytes one decode step of a latent-attention decoder with a
learned indexer (``model_type`` ``glm_moe_dsa``) has to read from device
memory: the floor under a step's time at the chip's bandwidth, and what
``decode_dsa_roofline_pct.serve`` divides by that time.

One step runs every layer once over one position a live row.  Whatever the
batch, it reads

* every weight that every token uses, once: the attention projections of
  every layer, the indexer's of every ``full`` layer, the dense layers'
  MLPs, the routers, the shared experts, the norms, the output head (the
  embedding is a gather of one row a token and is left out);
* of the routed experts held here, those that got at least one token in
  that layer and step (an expert with no token need not be read);
* on every layer, the cached latent and rotary key of the positions the
  live rows' attention reads: a row's own up to ``index_topk`` of them
  (``selected_positions``), at the values a token has and not at the
  pool's padded width;
* on every ``full`` layer, the index key of every position the live rows
  hold (``live_positions``): a selection has to score them all.

Writes, activations, the sort and the sampling are left out: a floor, so a
share read from it never flatters the program.  ``shape`` is a
configuration file's ``shape`` group (``harness/program.py: shape_of``).
"""

from __future__ import annotations

from typing import Any, Dict


def _gated_mlp(d: int, width: int) -> int:
    return 3 * d * width


def layer_counts(shape: Dict[str, Any]) -> Dict[str, int]:
    indexers, mlps = shape["indexer_types"], shape["mlp_layer_types"]
    layers = int(shape["num_hidden_layers"])
    if len(indexers) != layers or len(mlps) != layers:
        raise ValueError(
            f"indexer_types and mlp_layer_types must name {layers} layers")
    return {"layers": layers,
            "full": sum(t == "full" for t in indexers),
            "dense": sum(t == "dense" for t in mlps),
            "sparse": sum(t == "sparse" for t in mlps)}


def weight_parameters(shape: Dict[str, Any]) -> Dict[str, float]:
    """Parameters by the part of a step that reads them."""
    d, h = int(shape["hidden_size"]), int(shape["num_attention_heads"])
    nope, rot = int(shape["qk_nope_head_dim"]), int(shape["qk_rope_head_dim"])
    vd = int(shape["v_head_dim"])
    q_rank, kv_rank = int(shape["q_lora_rank"]), int(shape["kv_lora_rank"])
    hi, di = int(shape["index_n_heads"]), int(shape["index_head_dim"])
    n = layer_counts(shape)
    attention = (d * q_rank + q_rank + q_rank * h * (nope + rot)
                 + d * (kv_rank + rot) + kv_rank
                 + kv_rank * h * (nope + vd) + h * vd * d)
    indexer = q_rank * hi * di + d * di + 2 * di + d * hi
    norms = 2 * d
    expert = _gated_mlp(d, int(shape["moe_intermediate_size"]))
    router = d * int(shape["router_width"]) + int(shape["router_width"])
    return {
        "attention": n["layers"] * (attention + norms),
        "indexer": n["full"] * indexer,
        "dense_mlp": n["dense"] * _gated_mlp(d, int(shape["intermediate_size"])),
        "router": n["sparse"] * router,
        "shared_experts": n["sparse"] * int(shape["n_shared_experts"]) * expert,
        "head": d * int(shape["vocab_size"]) + d,
        "one_routed_expert": expert,
        "routed_experts_held": n["sparse"] * int(shape["experts_held"]) * expert,
    }


def decode_step_bytes(shape: Dict[str, Any], *, active_experts_per_layer: float,
                      live_positions: float,
                      selected_positions: float) -> Dict[str, float]:
    """Bytes a step reads at the least, by part and in all.
    ``active_experts_per_layer``: held experts that got a token, mean over
    expert layers and steps.  ``live_positions``: cached positions the
    step's live rows hold, all rows together; ``selected_positions``: the
    same with each row counted up to ``index_topk``."""
    held = int(shape["experts_held"])
    if not 0 <= active_experts_per_layer <= held:
        raise ValueError(
            f"active experts a layer {active_experts_per_layer} outside "
            f"0..{held}")
    if not 0 <= selected_positions <= live_positions:
        raise ValueError(
            f"selected positions {selected_positions} outside 0..live "
            f"positions {live_positions}")
    item = int(shape["parameter_bytes"])
    p = weight_parameters(shape)
    n = layer_counts(shape)
    latent = int(shape["kv_lora_rank"]) + int(shape["qk_rope_head_dim"])
    cache = float(shape["cache_bytes"])
    out = {
        "shared_weights": item * (
            p["attention"] + p["indexer"] + p["dense_mlp"] + p["router"]
            + p["shared_experts"] + p["head"]),
        "routed_experts": item * n["sparse"] * active_experts_per_layer
        * p["one_routed_expert"],
        "latent_cache": cache * n["layers"] * latent * selected_positions,
        "index_keys": cache * n["full"] * int(shape["index_head_dim"])
        * live_positions,
    }
    out["total"] = sum(out.values())
    return out
