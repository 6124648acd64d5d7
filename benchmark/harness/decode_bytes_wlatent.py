"""The least bytes one decode step of a latent-attention decoder with two
kinds of layer (``model_type`` ``dots3_note``: full layers that read a
learned indexer's selection, window layers with their own ranks and head
count) has to read from device memory: the floor under a step's time at the
chip's bandwidth, and what ``decode_wlatent_roofline_pct.serve`` divides by
that time.

One step runs every layer once over one position a live row.  Whatever the
batch, it reads

* every weight that every token uses, once: each layer's attention
  projections at its kind's sizes, the gates among them; the indexer's of
  every full layer; the dense layers' MLPs, the routers, the shared
  experts, the norms, the output head (the embedding is a gather of one
  row a token and is left out);
* of the routed experts held here, those that got at least one token in
  that layer and step (an expert with no token need not be read);
* on every full layer, the cached latent and rotary key of the positions
  the live rows' attention reads, a row's own up to ``index_topk`` of them
  (``selected_positions``), and the index key of every position the live
  rows hold (``live_positions``): a selection has to score them all;
* on every window layer, the cached latent and rotary key of the positions
  inside the live rows' windows, a row's own up to ``sliding_window_size``
  (``window_positions``).

Cached rows count at the values a token has (576 and 1,088) and not at the
pools' padded widths.  Writes, activations, the sort and the sampling are
left out: a floor, so a share read from it never flatters the program.
``shape`` is a configuration file's ``shape`` group
(``harness/program.py: shape_of``).
"""

from __future__ import annotations

from typing import Any, Dict

FULL, SLIDING = "full_attention", "sliding_attention"


def _gated_mlp(d: int, width: int) -> int:
    return 3 * d * width


def kind_sizes(shape: Dict[str, Any], layer_type: str) -> Dict[str, int]:
    """One kind's sizes under the plain keys."""
    prefix = "" if layer_type == FULL else "swa_"
    return {key: int(shape[prefix + key]) for key in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")}


def layer_counts(shape: Dict[str, Any]) -> Dict[str, int]:
    layers = int(shape["num_hidden_layers"])
    types = list(shape["layer_types"])[:layers]
    if len(types) != layers or set(types) - {FULL, SLIDING}:
        raise ValueError(f"layer_types must name {layers} layers of the two "
                         f"kinds, got {types}")
    dense = int(shape["first_k_dense_replace"])
    return {"layers": layers, "full": types.count(FULL),
            "window": types.count(SLIDING), "dense": dense,
            "sparse": layers - dense}


def attention_parameters(shape: Dict[str, Any], layer_type: str) -> int:
    """A layer's attention matrices and their norms' scales, the gate's
    among them."""
    s, d = kind_sizes(shape, layer_type), int(shape["hidden_size"])
    h, nope, rot = (s["num_attention_heads"], s["qk_nope_head_dim"],
                    s["qk_rope_head_dim"])
    return (d * s["q_lora_rank"] + s["q_lora_rank"]
            + s["q_lora_rank"] * h * (nope + rot)
            + d * (s["kv_lora_rank"] + rot) + s["kv_lora_rank"]
            + s["kv_lora_rank"] * h * (nope + s["v_head_dim"])
            + h * s["v_head_dim"] * d + d * h)


def weight_parameters(shape: Dict[str, Any]) -> Dict[str, float]:
    """Parameters by the part of a step that reads them."""
    d = int(shape["hidden_size"])
    hi, di = int(shape["index_n_heads"]), int(shape["index_head_dim"])
    n = layer_counts(shape)
    indexer = (int(shape["q_lora_rank"]) * hi * di + d * di + 2 * di
               + d * hi)
    expert = _gated_mlp(d, int(shape["moe_intermediate_size"]))
    router = d * int(shape["router_width"]) + int(shape["router_width"])
    return {
        "attention_full": n["full"] * (
            attention_parameters(shape, FULL) + 2 * d),
        "attention_window": n["window"] * (
            attention_parameters(shape, SLIDING) + 2 * d),
        "indexer": n["full"] * indexer,
        "dense_mlp": n["dense"] * _gated_mlp(
            d, int(shape["intermediate_size"])),
        "router": n["sparse"] * router,
        "shared_experts": n["sparse"] * int(shape["n_shared_experts"])
        * expert,
        "head": d * int(shape["vocab_size"]) + d,
        "one_routed_expert": expert,
        "routed_experts_held": n["sparse"] * int(shape["experts_held"])
        * expert,
    }


def decode_step_bytes(shape: Dict[str, Any], *, active_experts_per_layer: float,
                      live_positions: float, selected_positions: float,
                      window_positions: float) -> Dict[str, float]:
    """Bytes a step reads at the least, by part and in all.
    ``active_experts_per_layer``: held experts that got a token, mean over
    expert layers and steps.  ``live_positions``: cached positions the
    step's live rows hold, all rows together; ``selected_positions``: the
    same with each row counted up to ``index_topk``; ``window_positions``:
    the same with each row counted up to ``sliding_window_size``."""
    held = int(shape["experts_held"])
    if not 0 <= active_experts_per_layer <= held:
        raise ValueError(
            f"active experts a layer {active_experts_per_layer} outside "
            f"0..{held}")
    for name, counted in (("selected", selected_positions),
                          ("window", window_positions)):
        if not 0 <= counted <= live_positions:
            raise ValueError(
                f"{name} positions {counted} outside 0..live positions "
                f"{live_positions}")
    item = int(shape["parameter_bytes"])
    p = weight_parameters(shape)
    n = layer_counts(shape)
    cache = float(shape["cache_bytes"])
    full, window = kind_sizes(shape, FULL), kind_sizes(shape, SLIDING)
    row = lambda s: s["kv_lora_rank"] + s["qk_rope_head_dim"]
    out = {
        "shared_weights": item * (
            p["attention_full"] + p["attention_window"] + p["indexer"]
            + p["dense_mlp"] + p["router"] + p["shared_experts"]
            + p["head"]),
        "routed_experts": item * n["sparse"] * active_experts_per_layer
        * p["one_routed_expert"],
        "selected_latent": cache * n["full"] * row(full)
        * selected_positions,
        "index_keys": cache * n["full"] * int(shape["index_head_dim"])
        * live_positions,
        "window_latent": cache * n["window"] * row(window)
        * window_positions,
    }
    out["total"] = sum(out.values())
    return out
