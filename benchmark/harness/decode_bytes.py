"""The least bytes one decode step of a latent-attention, sparse-expert
decoder has to read from device memory: the floor under a step's time at
the chip's bandwidth, and what ``decode_hbm_roofline_pct.serve`` divides by
that time.

One step runs every layer once over one position a live row.  Whatever the
batch, it reads

* every weight that every token uses, once: the attention projections of
  every layer, the dense layers' MLPs, the routers, the shared experts, the
  norms, the output head (the embedding is a gather of one row a token and
  is left out);
* of the routed experts held here, those that got at least one token in
  that layer and step (an expert with no token need not be read);
* the cached latent and rotary key of every position the live rows hold,
  once a layer, at the values a token has and not at the pool's padded
  width.

Writes (one new row a layer and live row), activations and the sampling
are left out: a floor, so a share read from it never flatters the
program.  ``shape`` is a configuration file's ``shape`` group
(``harness/program.py: shape_of``).
"""

from __future__ import annotations

from typing import Any, Dict


def _gated_mlp(d: int, width: int) -> int:
    return 3 * d * width


def weight_parameters(shape: Dict[str, Any]) -> Dict[str, float]:
    """Parameters by the part of a step that reads them."""
    d, h = int(shape["hidden_size"]), int(shape["num_attention_heads"])
    nope, rot = int(shape["qk_nope_head_dim"]), int(shape["qk_rope_head_dim"])
    vd = int(shape["v_head_dim"])
    q_rank, kv_rank = int(shape["q_lora_rank"]), int(shape["kv_lora_rank"])
    layers = int(shape["num_hidden_layers"])
    dense = int(shape["first_k_dense_replace"])
    moe = layers - dense
    attention = (d * q_rank + q_rank + q_rank * h * (nope + rot)
                 + d * (kv_rank + rot) + kv_rank
                 + kv_rank * h * (nope + vd) + h * vd * d)
    norms = 2 * d
    expert = _gated_mlp(d, int(shape["moe_intermediate_size"]))
    router = d * int(shape["router_width"]) + int(shape["router_width"])
    return {
        "attention": layers * (attention + norms),
        "dense_mlp": dense * _gated_mlp(d, int(shape["intermediate_size"])),
        "router": moe * router,
        "shared_experts": moe * int(shape["n_shared_experts"]) * expert,
        "head": d * int(shape["vocab_size"]) + d,
        "one_routed_expert": expert,
        "routed_experts_held": moe * int(shape["experts_held"]) * expert,
    }


def decode_step_bytes(shape: Dict[str, Any], *, active_experts_per_layer: float,
                      live_positions: float) -> Dict[str, float]:
    """Bytes a step reads at the least, by part and in all.
    ``active_experts_per_layer``: held experts that got a token, mean over
    expert layers and steps.  ``live_positions``: cached positions the
    step's live rows hold, all rows together."""
    held = int(shape["experts_held"])
    if not 0 <= active_experts_per_layer <= held:
        raise ValueError(
            f"active experts a layer {active_experts_per_layer} outside "
            f"0..{held}")
    if live_positions < 0:
        raise ValueError(f"live positions {live_positions} < 0")
    item = int(shape["parameter_bytes"])
    p = weight_parameters(shape)
    layers = int(shape["num_hidden_layers"])
    moe = layers - int(shape["first_k_dense_replace"])
    latent = int(shape["kv_lora_rank"]) + int(shape["qk_rope_head_dim"])
    out = {
        "shared_weights": item * (p["attention"] + p["dense_mlp"] + p["router"]
                                  + p["shared_experts"] + p["head"]),
        "routed_experts": item * moe * active_experts_per_layer
        * p["one_routed_expert"],
        "latent_cache": float(shape["cache_bytes"]) * layers * latent
        * live_positions,
    }
    out["total"] = sum(out.values())
    return out
