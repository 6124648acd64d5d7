"""The least bytes one decode step of a decoder of gated delta-rule linear
attention with grouped-query layers between (``model_type``
``solar_open2``) has to move through device memory: the floor under a
step's time at the chip's bandwidth, and what
``decode_state_roofline_pct.serve`` divides by that time.

One step runs every layer once over one position a live row.  Whatever the
batch, it moves

* every weight that every token uses, once: the mixers' projections of
  every layer (a GQA layer's ``q``, ``k``, ``v``, gate and ``o``; a linear
  layer's ``qkv``, convolution, the two low-rank gates, ``beta``, the
  decay's leaves, the head norm and ``o``), the routers, the shared
  experts, the norms, the output head (the embedding is a gather of one row
  a token and is left out);
* of the routed experts held here, those that got at least one token in
  that layer and step (an expert with no token need not be read);
* on every linear layer, each live row's recurrent state, ``heads x
  head_dim x head_dim`` float32 values, READ AND WRITTEN once (a step
  overwrites it: the write is no less required than the read), and its
  convolution tail (``kernel - 1`` positions of three projections), read
  and written likewise;
* on every GQA layer, the cached K and V of the positions the live rows
  hold.

The K/V rows a step writes, activations and the sampling are left out: a
floor, so a share read from it never flatters the program.  ``shape`` is a
configuration file's ``shape`` group (``harness/program.py: shape_of``).
"""

from __future__ import annotations

from typing import Any, Dict


def layer_counts(shape: Dict[str, Any]) -> Dict[str, int]:
    layers = int(shape["num_hidden_layers"])
    gqa = sum(int(l) < layers for l in shape["gqa_layers"])
    return {"layers": layers, "gqa": gqa, "kda": layers - gqa}


def kda_width(shape: Dict[str, Any]) -> int:
    return int(shape["kda_num_heads"]) * int(shape["kda_head_dim"])


def state_values_per_slot_layer(shape: Dict[str, Any]) -> int:
    return int(shape["kda_num_heads"]) * int(shape["kda_head_dim"]) ** 2


def tail_values_per_slot_layer(shape: Dict[str, Any]) -> int:
    return (int(shape["kda_conv_size"]) - 1) * 3 * kda_width(shape)


def kv_values_per_position(shape: Dict[str, Any]) -> int:
    return 2 * int(shape["num_key_value_heads"]) * int(shape["head_dim"])


def weight_parameters(shape: Dict[str, Any]) -> Dict[str, float]:
    """Parameters by the part of a step that reads them."""
    d, hd = int(shape["hidden_size"]), int(shape["head_dim"])
    wide = int(shape["num_attention_heads"]) * hd
    narrow = int(shape["num_key_value_heads"]) * hd
    f, r = kda_width(shape), int(shape["kda_gate_rank"])
    n = layer_counts(shape)
    gqa = 3 * d * wide + 2 * d * narrow               # q, gate, o; k, v
    kda = (3 * d * f + int(shape["kda_conv_size"]) * 3 * f
           + 2 * (d * r + r * f) + d * int(shape["kda_num_heads"])
           + int(shape["kda_num_heads"]) + f + int(shape["kda_head_dim"])
           + f * d)
    expert = 3 * d * int(shape["moe_intermediate_size"])
    width = int(shape["router_width"])
    return {
        "gqa_mixers": n["gqa"] * gqa,
        "kda_mixers": n["kda"] * kda,
        "norms": n["layers"] * 2 * d,
        "router": n["layers"] * (d * width + width),
        "shared_experts": n["layers"] * int(shape["n_shared_experts"]) * expert,
        "head": d * int(shape["vocab_size"]) + d,
        "one_routed_expert": expert,
        "routed_experts_held": n["layers"] * int(shape["experts_held"]) * expert,
    }


def decode_step_bytes(shape: Dict[str, Any], *, active_experts_per_layer: float,
                      live_rows: float, live_positions: float
                      ) -> Dict[str, float]:
    """Bytes a step moves at the least, by part and in all.
    ``active_experts_per_layer``: held experts that got a token, mean over
    layers and steps.  ``live_rows``: rows the step runs; ``live_positions``:
    cached positions they hold, all rows together."""
    held = int(shape["experts_held"])
    if not 0 <= active_experts_per_layer <= held:
        raise ValueError(
            f"active experts a layer {active_experts_per_layer} outside "
            f"0..{held}")
    if live_rows < 0 or live_positions < live_rows:
        raise ValueError(
            f"{live_rows} live rows holding {live_positions} positions")
    item = int(shape["parameter_bytes"])
    p = weight_parameters(shape)
    n = layer_counts(shape)
    out = {
        "shared_weights": item * (
            p["gqa_mixers"] + p["kda_mixers"] + p["norms"] + p["router"]
            + p["shared_experts"] + p["head"]),
        "routed_experts": item * n["layers"] * active_experts_per_layer
        * p["one_routed_expert"],
        "state": 2.0 * float(shape["state_bytes"]) * n["kda"]
        * state_values_per_slot_layer(shape) * live_rows,
        "conv_tails": 2.0 * float(shape["cache_bytes"]) * n["kda"]
        * tail_values_per_slot_layer(shape) * live_rows,
        "kv_cache": float(shape["cache_bytes"]) * n["gqa"]
        * kv_values_per_position(shape) * live_positions,
    }
    out["total"] = sum(out.values())
    return out
