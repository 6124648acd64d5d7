"""A latent-attention, sparse-expert decoder (``model_type`` ``glm4_moe_lite``)
for the serving path.

The second decoder family beside ``models/gpt2.py``: RMS norm, rotary
positions, gated MLPs, an untied head, one leading dense layer and then a
scanned stack of expert layers (``models/decoder_parts.py``: the layers no
family owns, the expert layer that is told which experts it holds and its
``moe_counts`` row, the rule of precision).  What is this family's own:

- **Latent attention (MLA)** (``decoder_parts.mla_*``; the indexed latent
  family shares it).  Queries go through a low-rank bottleneck
  (``q_lora_rank``); keys and values are up-projections (``kv_b``) of one
  normalized latent of ``kv_lora_rank`` values a token, beside one rotary
  key of ``qk_rope_head_dim`` values shared by every head.  The cache holds
  that latent and that key and nothing else: ``kv_lora_rank +
  qk_rope_head_dim`` values a token and layer, whatever the head count, in a
  lane-dense paged pool ``(layers, num_blocks, block_size, pool_width)``
  addressed through the scheduler's block tables (``PagedKVConfig``, the
  geometry every family shares; only the row's content differs;
  ``models/paged_call.py`` is the call's view of it).
  Attention over the pool runs one of two ways, chosen by the call's shape
  and recorded like the paged kernel's choice (``ops.paged_attention.
  note_path``): a decode step (one query a row) **absorbs** ``kv_b`` into
  the query and applies its value half after the softmax, so the pool's
  latent rows are the keys and the values of one 576-wide head shared by
  all (``ABSORBED``); every longer call **expands** the gathered latents to
  per-head keys and values first (``EXPANDED``).  Same mathematics, and
  ``tests/test_glm4_moe_lite.py`` holds them to each other.
- **A sigmoid router with a correction bias** (``route``'s
  ``"sigmoid_bias"`` kind): the top ``num_experts_per_tok`` of score +
  bias; weights the chosen scores, normalized, times
  ``routed_scaling_factor``; one shared expert beside the routed ones.
  ``experts_held == n_routed_experts`` is the uncut layer.

The multi-token-prediction layer of the published model is not built:
the main model's logits do not depend on it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax.numpy as jnp
from flax import linen as nn
from jax import lax
from jax.sharding import Mesh

from distributed_tensorflow_tpu.models import PagedKVConfig, Workload
from distributed_tensorflow_tpu.models.decoder_parts import (
    check_share, declare, expert_layer, gated_mlp, mla_attend, mla_cache_row,
    mla_output, mla_project, mla_sizes, mla_spec, mlp_spec, rms_norm, stacked)
from distributed_tensorflow_tpu.models.paged_call import (
    PagedCall, decoder_workload, serve_refusals)
from distributed_tensorflow_tpu.ops import paged_attention

# The latent attention's two implementations, as ``attention_paths()``
# names them.
ABSORBED, EXPANDED = "latent_absorbed", "latent_expanded"

_LANES = 128


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
    """Published keys of ``config.json`` under their own names, plus the
    share of the expert layer this device holds."""

    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240          # the leading dense layers' MLP
    moe_intermediate_size: int = 1536       # one expert, routed or shared
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64              # the router's width, as published
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 202752
    # This device's share: ``experts_held`` consecutive experts starting at
    # ``first_expert``.  None holds them all (the uncut layer).
    experts_held: Optional[int] = None
    first_expert: int = 0
    dtype: Any = jnp.bfloat16               # products' operands, parameters

    router = "sigmoid_bias"                 # ``route``'s kind; no field

    def __post_init__(self):
        check_share(self, self.n_routed_experts, "n_routed_experts")
        if not 1 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(
                "first_k_dense_replace must leave at least one dense layer "
                f"and fit num_hidden_layers {self.num_hidden_layers}, got "
                f"{self.first_k_dense_replace}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotary pairs)")

    @property
    def held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else int(self.experts_held))

    @property
    def n_dense_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values cached a token and layer: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def pool_width(self) -> int:
        """``latent_width`` rounded up to whole lane tiles."""
        return -(-self.latent_width // _LANES) * _LANES

    @property
    def n_positions(self) -> int:
        """What the engine checks a slot's length against."""
        return self.max_position_embeddings

    @classmethod
    def flash(cls, **kw):
        """The published GLM-4.7-Flash sizes, every expert held."""
        return cls(**kw)

    @classmethod
    def v5e8_share(cls, **kw):
        """One chip's share of a v5e-8 host on which 8 chips share each
        layer (8 experts a layer, 1/8 of the vocabulary's rows), at the
        depth one chip serves beside its float32 reference: the sizes of
        ``benchmark/configs/glm-4.7-flash.json``."""
        base = dict(num_hidden_layers=21, vocab_size=19360, experts_held=8)
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny(cls, **kw):  # tests
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=32, kv_lora_rank=64,
            qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=32,
            n_routed_experts=8, num_experts_per_tok=2,
            max_position_embeddings=256)
        base.update(kw)
        return cls(**base)


# -- parameters ----------------------------------------------------------------

def _layer_spec(cfg, moe: bool):
    d = cfg.hidden_size
    common = (("input_norm", (("scale", (d,)),)),
              ("attn", mla_spec(cfg, mla_sizes(cfg))),
              ("post_norm", (("scale", (d,)),)))
    if not moe:
        return common + (("mlp", mlp_spec(d, cfg.intermediate_size)),)
    shared = cfg.n_shared_experts * cfg.moe_intermediate_size
    return common + (
        ("router", (("kernel", (d, cfg.n_routed_experts)),
                    ("bias", (cfg.n_routed_experts,)))),
        ("shared", mlp_spec(d, shared)),
        ("experts", mlp_spec(d, cfg.moe_intermediate_size,
                             lead=(cfg.held,))),
    )


def param_spec(cfg):
    d = cfg.hidden_size
    return (
        ("embed", (cfg.vocab_size, d)),
        ("dense_layers", stacked(_layer_spec(cfg, False),
                                 cfg.n_dense_layers)),
        ("moe_layers", stacked(_layer_spec(cfg, True), cfg.n_moe_layers)),
        ("final_norm", (("scale", (d,)),)),
        ("head", (("kernel", (d, cfg.vocab_size)),)),
    )


# -- the module ----------------------------------------------------------------

class Glm4MoeLite(nn.Module):
    cfg: Glm4MoeLiteConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, *, deterministic: bool = True,
                 decode: bool = False, slot_ids=None,
                 paged: Optional[PagedKVConfig] = None, block_tables=None,
                 live=None):
        cfg = self.cfg
        B, T = tokens.shape
        params = declare(self, param_spec(cfg), cfg)
        sizes = mla_sizes(cfg)
        # The residual stream is float32 from here to the head: what a
        # layer adds is a product's float32 result, and the router reads
        # the stream's norm unrounded (a rounded one flips its near ties).
        x = params["embed"][tokens].astype(jnp.float32)
        view = PagedCall(
            self, B, T, decode=decode, slot_ids=slot_ids, paged=paged,
            block_tables=block_tables, live=live, pools="the latent pool",
            refusals=SERVE_REFUSALS, experts=(cfg.n_moe_layers, cfg.held))
        pool_value = view.pool("latent_pool", cfg.num_hidden_layers,
                               cfg.pool_width, cfg.dtype)
        positions = view.positions
        if decode:
            span = view.table.shape[1] * paged.block_size
            mask = (jnp.arange(span)[None, None, :]
                    <= positions[:, :, None])                     # (B, T, S)
            absorb = T == 1
            paged_attention.note_path(ABSORBED if absorb else EXPANDED)
        else:
            mask = jnp.broadcast_to(
                jnp.tril(jnp.ones((T, T), bool))[None], (B, T, T))
            absorb = False
        view.advance()
        token_live = view.token_live

        def attention(p, x, pool_value, layer):
            xn = rms_norm(x, p["input_norm"]["scale"],
                          cfg.rms_norm_eps).astype(cfg.dtype)
            q_n, q_r, latent, k_r = mla_project(
                cfg, sizes, p["attn"], xn, positions)
            if pool_value is not None:
                pool_value = view.write(
                    pool_value, layer, mla_cache_row(cfg, sizes, latent, k_r))
                # The slot's whole table row, gathered back: positions past
                # the row's index (and trash entries) are masked.
                rows = view.gather(pool_value, layer)
                latent = rows[..., :cfg.kv_lora_rank]
                k_r = rows[..., cfg.kv_lora_rank:cfg.latent_width]
            ctx = mla_attend(cfg, sizes, p["attn"], q_n, q_r, latent, k_r,
                             mask, absorb)
            return x + mla_output(cfg, sizes, p["attn"], xn, ctx), pool_value

        def dense_layer(carry, xs):
            x, pool_value = carry
            p, layer = xs
            h, pool_value = attention(p, x, pool_value, layer)
            hn = rms_norm(h, p["post_norm"]["scale"],
                          cfg.rms_norm_eps).astype(cfg.dtype)
            return (h + gated_mlp(p["mlp"], hn, cfg.dtype), pool_value), None

        def moe_layer(carry, xs):
            x, pool_value = carry
            p, layer = xs
            h, pool_value = attention(p, x, pool_value, layer)
            hn = rms_norm(h, p["post_norm"]["scale"], cfg.rms_norm_eps)
            # The experts' stacks whole, and which layer's to read.
            y, row = expert_layer(
                cfg, dict(p, experts=params["moe_layers"]["experts"]),
                hn.reshape(B * T, cfg.hidden_size), token_live,
                layer=layer - nd, mesh=self.mesh)
            return (h + y.reshape(h.shape), pool_value), row

        nd = cfg.n_dense_layers
        carry = (x, pool_value)
        carry, _ = lax.scan(
            dense_layer, carry,
            (params["dense_layers"], jnp.arange(nd, dtype=jnp.int32)))
        carry, rows = lax.scan(
            moe_layer, carry,
            ({name: leaves for name, leaves in params["moe_layers"].items()
              if name != "experts"},
             nd + jnp.arange(cfg.n_moe_layers, dtype=jnp.int32)))
        x, pool_value = carry
        view.close(pool_value, counts=rows)
        return view.head(params, x)


# -- what the engine and the scheduler ask of a decoder family -----------------

def cache_geometry(cfg: Glm4MoeLiteConfig, paged: PagedKVConfig
                   ) -> Dict[str, Any]:
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return {
        "kind": "latent",
        "pools_per_layer": 1,
        "values_per_token_layer": cfg.latent_width,
        "pool_width": cfg.pool_width,
        "padding_values": cfg.pool_width - cfg.latent_width,
        "bytes_per_token_layer": cfg.pool_width * itemsize,
        "bytes_per_token": cfg.num_hidden_layers * cfg.pool_width * itemsize,
        "pool_bytes": (cfg.num_hidden_layers * paged.num_blocks
                       * paged.block_size * cfg.pool_width * itemsize),
    }


SERVE_REFUSALS = serve_refusals("the latent pool")


def make_workload(*, preset: str = "flash",
                  config: Optional[Glm4MoeLiteConfig] = None,
                  mesh: Optional[Mesh] = None, **kw) -> Workload:
    cfg = config or getattr(Glm4MoeLiteConfig, preset)()
    return decoder_workload("glm4_moe_lite", Glm4MoeLite, cfg, mesh,
                            cache_geometry, SERVE_REFUSALS, **kw)
