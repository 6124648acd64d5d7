"""The least bytes one decode step of a grouped-query decoder with window
and full attention layers and sparse experts has to read from device
memory: the floor under a step's time at the chip's bandwidth, and what
``decode_kv_roofline_pct.serve`` divides by that time.

One step runs every layer once over one position a live row.  Whatever the
batch, it reads

* every weight that every token uses, once: the attention projections of
  every layer (``q`` and ``o`` at the query head count, ``k`` and ``v`` at
  the K/V head count), the routers, the norms, the output head (the
  embedding is a gather of one row a token and is left out; there is no
  shared expert and no dense MLP);
* of the routed experts held here, those that got at least one token in
  that layer and step (an expert with no token need not be read);
* the cached K and V of the live rows, ``2 x num_key_value_heads x
  head_dim`` values a position and layer: a full layer reads every
  position a row holds, a window layer at most the window's.

Writes (one new row a layer and live row), activations and the sampling
are left out: a floor, so a share read from it never flatters the
program.  ``shape`` is a configuration file's ``shape`` group
(``harness/program.py: shape_of``).
"""

from __future__ import annotations

from typing import Any, Dict


def kv_values_per_position(shape: Dict[str, Any]) -> int:
    return 2 * int(shape["num_key_value_heads"]) * int(shape["head_dim"])


def weight_parameters(shape: Dict[str, Any]) -> Dict[str, float]:
    """Parameters by the part of a step that reads them."""
    d, hd = int(shape["hidden_size"]), int(shape["head_dim"])
    hq = int(shape["num_attention_heads"])
    hkv = int(shape["num_key_value_heads"])
    layers = int(shape["num_hidden_layers"])
    attention = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    expert = 3 * d * int(shape["moe_intermediate_size"])
    return {
        "attention": layers * (attention + 2 * d),
        "router": layers * d * int(shape["router_width"]),
        "head": d * int(shape["vocab_size"]) + d,
        "one_routed_expert": expert,
        "routed_experts_held": layers * int(shape["experts_held"]) * expert,
    }


def decode_step_bytes(shape: Dict[str, Any], *, active_experts_per_layer: float,
                      live_positions_full: float,
                      live_positions_window: float) -> Dict[str, float]:
    """Bytes a step reads at the least, by part and in all.
    ``active_experts_per_layer``: held experts that got a token, mean over
    layers and steps.  ``live_positions_full``: cached positions the step's
    live rows hold, all rows together; ``live_positions_window``: the same
    with each row counted up to the window."""
    held = int(shape["experts_held"])
    if not 0 <= active_experts_per_layer <= held:
        raise ValueError(
            f"active experts a layer {active_experts_per_layer} outside "
            f"0..{held}")
    if not 0 <= live_positions_window <= live_positions_full:
        raise ValueError(
            f"live positions: window {live_positions_window} must lie in "
            f"0..full {live_positions_full}")
    full = int(shape["full_attention_layers"])
    window = int(shape["sliding_attention_layers"])
    layers = int(shape["num_hidden_layers"])
    if full + window != layers:
        raise ValueError(f"{full} full + {window} window layers != {layers}")
    item = int(shape["parameter_bytes"])
    p = weight_parameters(shape)
    row = float(shape["cache_bytes"]) * kv_values_per_position(shape)
    out = {
        "shared_weights": item * (p["attention"] + p["router"] + p["head"]),
        "routed_experts": item * layers * active_experts_per_layer
        * p["one_routed_expert"],
        "kv_full_layers": row * full * live_positions_full,
        "kv_window_layers": row * window * live_positions_window,
    }
    out["total"] = sum(out.values())
    return out
