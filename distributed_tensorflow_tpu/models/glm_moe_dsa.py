"""A latent-attention, sparse-expert decoder whose attention reads only the
positions a learned indexer selects (``model_type`` ``glm_moe_dsa``), for the
serving path.

The fourth decoder family.  RMS norm, rotate-half rotary positions, the
parameters' declaration, the latent projections (``mla_project``), the
absorbed and the expanded latent attention (``mla_attend``), the indexer's
projections, scores and selection (``indexer_project``, ``index_scores``,
``select_mask``, ``select_top``), the float32 sigmoid router and the expert
layer that is told which experts it holds are ``models/decoder_parts.py``'s;
a position's pool cell, the row write and the walk over a long context
through the table (``ContextWalk``) are ``models/paged_call.py``'s: a second
family has an indexer too.  What this family does with them:

- **The indexer** (a ``full`` layer).  From the query's low-rank latent
  ``c_q``: ``q^I = c_q W^I_q``, ``index_n_heads`` heads of
  ``index_head_dim``, the first ``qk_rope_head_dim`` of each rotated; from
  the normalized input ``xn``: one key a position ``k^I = layer_norm(xn
  W^I_k)`` (rotated the same way) and head weights ``w = xn W^I_w *
  index_n_heads^-1/2 * index_head_dim^-1/2``.  A position's score for a
  query is ``I[t, s] = sum_h w[t, h] relu(q^I[t, h] . k^I[s])``, float32,
  and the query reads ``S_t``: the ``min(t + 1, index_topk)`` positions ``s
  <= t`` of largest score, the lower position first on a tie.
- **Shared indices.**  ``indexer_types[l]`` is ``full`` or ``shared``; a
  ``shared`` layer has no indexer and no index keys and reads the set of
  the nearest ``full`` layer before it, handed down the layer loop.
- **Attention over the selection alone**: softmax over ``s in S_t``.  Up to
  ``index_topk`` positions of context that is plain causal attention.
- **Two paged pools under one block table.**  The latent pool ``(layers,
  num_blocks, block_size, pool_width)`` as ``glm4_moe_lite`` keeps it, and
  beside it the index keys' ``(full layers, num_blocks, block_size,
  index_head_dim)``: physical block ``b`` of the one is block ``b`` of the
  other, so the scheduler's allocator, its tables and its trash block serve
  both and a block is taken, freed and counted once.

A cached call runs one of two ways, on record like the other families'
(``ops.paged_attention.note_path``):

- ``SELECTED`` (a decode step, one query a row): a ``full`` layer scores the
  row's cached index keys chunk by chunk through the block table, as far as
  the longest live row reaches and no further, takes the top ``index_topk``
  (``select_top``) and turns them into pool cells through the table once;
  every layer down to the next ``full`` one gathers those cells' latent
  rows, ``index_topk`` a row and layer at most, and attends absorbed.  No
  table row is gathered whole.
- ``MASKED`` (a prefill chunk): a ``full`` layer scores every query of the
  chunk against the context's index keys and marks each query's selection
  in a mask ``(B, T, context)`` (the ``index_topk``-th largest score found
  by bisection on the scores' bits: a mask needs no sort); every layer
  attends expanded, context chunk by context chunk under an online softmax,
  as far as the call's last position reaches.  A per-query gather of
  ``index_topk`` latent rows would move ``T`` times the bytes of the
  context it selects from; the MXU does the unselected pairs for less.

An uncached call (``decode=False``: the tests' full forward, a loss) takes
the same scores and the same mask over its own positions.

``mlp_layer_types[l]`` is ``dense`` or ``sparse``.  The layers are four
kinds (``dense_full``, ``sparse_shared``...) in no fixed period, so each is
a group of leaves of its own (``layer_0``...) and the layer loop is written
out, not scanned: a chip of the deployment holds one pipeline stage's ten
or so layers, never the 78.

Precision as ``decoder_parts`` has it; index keys are cached in the compute type
and index scores are float32 sums of bfloat16 products.  Not built: the
multi-token-prediction layer (the main logits do not depend on it), the
published indexer's float8 keys and its Hadamard rotation (an orthogonal
map on both sides leaves ``q^I . k^I`` as it is).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import Mesh

from distributed_tensorflow_tpu.models import PagedKVConfig, Workload
from distributed_tensorflow_tpu.models.decoder_parts import (
    check_share, declare, expert_layer, gated_mlp, index_scores,
    indexer_project, indexer_spec, mla_attend, mla_cache_row, mla_output,
    mla_project,
    mla_query_latent, mla_sizes, mla_spec, mlp_spec, rms_norm, select_mask,
    select_top)
from distributed_tensorflow_tpu.models.paged_call import (
    ContextWalk, PagedCall, decoder_workload, serve_refusals)
from distributed_tensorflow_tpu.ops import paged_attention

# The cached attention's two implementations, as ``attention_paths()`` names
# them.
SELECTED, MASKED = "latent_sparse_selected", "latent_sparse_masked"

FULL, SHARED = "full", "shared"
DENSE, SPARSE = "dense", "sparse"


@dataclasses.dataclass(frozen=True)
class GlmMoeDsaConfig:
    """Published keys of ``config.json`` under their own names, plus the
    share of the expert layer this device holds."""

    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288          # a dense layer's MLP
    moe_intermediate_size: int = 2048       # one expert, routed or shared
    num_hidden_layers: int = 78
    first_k_dense_replace: int = 3
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    # None: ``full`` on the leading dense layers and then on every fourth
    # layer (``shared, shared, shared, full``), as published.
    indexer_types: Optional[Tuple[str, ...]] = None
    # None: ``dense`` on the first ``first_k_dense_replace`` layers.
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    n_routed_experts: int = 256             # the router's width, as published
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6            # the index key's layer norm
    rope_theta: float = 8e6
    max_position_embeddings: int = 1048576
    # This device's share: ``experts_held`` consecutive experts starting at
    # ``first_expert``.  None holds them all (the uncut layer).
    experts_held: Optional[int] = None
    first_expert: int = 0
    dtype: Any = jnp.bfloat16               # products' operands, parameters

    router = "sigmoid_bias"                 # ``decoder_parts.route``'s kind

    def __post_init__(self):
        put = lambda name, value: object.__setattr__(self, name, value)
        n, k = self.num_hidden_layers, self.first_k_dense_replace
        indexers = self.indexer_types
        if indexers is None:
            indexers = tuple(
                FULL if l < k or (l - k) % 4 == 3 else SHARED
                for l in range(n))
        mlps = self.mlp_layer_types
        if mlps is None:
            mlps = tuple(DENSE if l < k else SPARSE for l in range(n))
        indexers, mlps = tuple(indexers), tuple(mlps)
        if len(indexers) != n or set(indexers) - {FULL, SHARED}:
            raise ValueError(
                f"indexer_types must name {n} layers, each {FULL!r} or "
                f"{SHARED!r}, got {indexers}")
        if indexers[0] != FULL:
            raise ValueError(
                "indexer_types must start with a 'full' layer: a 'shared' "
                "layer reads the selection of the 'full' layer before it")
        if len(mlps) != n or set(mlps) - {DENSE, SPARSE}:
            raise ValueError(
                f"mlp_layer_types must name {n} layers, each {DENSE!r} or "
                f"{SPARSE!r}, got {mlps}")
        put("indexer_types", indexers)
        put("mlp_layer_types", mlps)
        check_share(self, self.n_routed_experts, "n_routed_experts")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotary pairs)")
        if self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError(
                f"index_head_dim {self.index_head_dim} must hold the "
                f"{self.qk_rope_head_dim} rotated dimensions")
        if self.index_topk < 1:
            raise ValueError("index_topk must be >= 1")

    @property
    def held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else int(self.experts_held))

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's stack: its MLP's kind and its indexer's."""
        return tuple(f"{mlp}_{indexer}" for mlp, indexer in zip(
            self.mlp_layer_types, self.indexer_types))

    @property
    def n_full_layers(self) -> int:
        return sum(t == FULL for t in self.indexer_types)

    @property
    def n_moe_layers(self) -> int:
        return sum(t == SPARSE for t in self.mlp_layer_types)

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
        return -(-self.latent_width // 128) * 128

    @property
    def n_positions(self) -> int:
        """What the engine checks a slot's length against."""
        return self.max_position_embeddings

    @classmethod
    def published(cls, **kw):
        """GLM-5.2's sizes, every expert held."""
        return cls(**kw)

    @classmethod
    def v5e256_share(cls, **kw):
        """One chip's share of a v5e-256 on which 32 chips share each layer
        (8 experts a layer, 1/8 of the vocabulary's rows), at the depth one
        chip serves beside its float32 reference: published layers 2-6 (a
        dense layer and one whole period, ``full`` at both ends), the
        sizes of ``benchmark/configs/glm-5.2.json``."""
        base = dict(num_hidden_layers=5, first_k_dense_replace=1,
                    indexer_types=(FULL, SHARED, SHARED, SHARED, FULL),
                    vocab_size=19360, experts_held=8)
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny(cls, **kw):  # tests
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=5,
            first_k_dense_replace=1,
            indexer_types=(FULL, SHARED, SHARED, SHARED, FULL),
            num_attention_heads=4, q_lora_rank=32, kv_lora_rank=64,
            qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=32,
            index_n_heads=4, index_head_dim=32, index_topk=24,
            n_routed_experts=8, num_experts_per_tok=2,
            max_position_embeddings=512)
        base.update(kw)
        return cls(**base)


# -- parameters ----------------------------------------------------------------

def _layer_spec(cfg, kind: str):
    mlp, indexer = kind.split("_")
    d = cfg.hidden_size
    sizes = mla_sizes(cfg)
    spec = (("input_norm", (("scale", (d,)),)),
            ("attn", mla_spec(cfg, sizes)))
    if indexer == FULL:
        spec += (("indexer", indexer_spec(cfg, sizes)),)
    spec += (("post_norm", (("scale", (d,)),)),)
    if mlp == DENSE:
        return spec + (("mlp", mlp_spec(d, cfg.intermediate_size)),)
    shared = cfg.n_shared_experts * cfg.moe_intermediate_size
    return spec + (
        ("router", (("kernel", (d, cfg.n_routed_experts)),
                    ("bias", (cfg.n_routed_experts,)))),
        ("shared", mlp_spec(d, shared)),
        ("experts", mlp_spec(d, cfg.moe_intermediate_size,
                             lead=(cfg.held,))),
    )


def param_spec(cfg):
    """``embed``, a group a layer (``layer_0``...; what a group holds is
    its layer's kind's to say), ``final_norm``, ``head``."""
    d = cfg.hidden_size
    layers = tuple((f"layer_{l}", _layer_spec(cfg, kind))
                   for l, kind in enumerate(cfg.layer_kinds))
    return ((("embed", (cfg.vocab_size, d)),) + layers + (
        ("final_norm", (("scale", (d,)),)),
        ("head", (("kernel", (d, cfg.vocab_size)),)),
    ))


# -- the module ----------------------------------------------------------------

class GlmMoeDsa(nn.Module):
    cfg: GlmMoeDsaConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, *, deterministic: bool = True,
                 decode: bool = False, slot_ids=None,
                 paged: Optional[PagedKVConfig] = None, block_tables=None,
                 live=None):
        cfg = self.cfg
        B, T = tokens.shape
        params = declare(self, param_spec(cfg), cfg)
        dt, sizes = cfg.dtype, mla_sizes(cfg)
        rank, lw = sizes.kv_lora_rank, sizes.latent_width
        # Float32 from here to the head.
        x = params["embed"][tokens].astype(jnp.float32)
        view = PagedCall(
            self, B, T, decode=decode, slot_ids=slot_ids, paged=paged,
            block_tables=block_tables, live=live,
            pools="the latent and index-key pools", refusals=SERVE_REFUSALS,
            experts=(cfg.n_moe_layers, cfg.held))
        positions = view.positions
        if decode:
            walk = ContextWalk(view)
            causal = walk.causal
            paged_attention.note_path(SELECTED if T == 1 else MASKED)
        else:
            causal = jnp.broadcast_to(
                jnp.tril(jnp.ones((T, T), bool))[None], (B, T, T))
        view.advance()
        pools = (
            view.pool("latent_pool", cfg.num_hidden_layers, cfg.pool_width,
                      dt),
            view.pool("index_pool", cfg.n_full_layers, cfg.index_head_dim,
                      dt))
        token_live = view.token_live

        def attention(p, x, pools, layer, full_layer, selection):
            """One layer's attention; ``full_layer`` is the layer's place
            among the ``full`` ones (None on a ``shared`` layer, which
            reads ``selection`` as the last ``full`` layer left it)."""
            latent_pool_v, index_pool_v = pools
            xn = rms_norm(x, p["input_norm"]["scale"],
                          cfg.rms_norm_eps).astype(dt)
            cq = mla_query_latent(cfg, sizes, p["attn"], xn)
            q_n, q_r, latent, k_r = mla_project(
                cfg, sizes, p["attn"], xn, positions, cq=cq)
            if full_layer is not None:
                q_i, k_i, w = indexer_project(
                    cfg, sizes, p["indexer"], xn, cq, positions)
            if latent_pool_v is None:
                if full_layer is not None:
                    selection = causal & select_mask(jnp.where(
                        causal, index_scores(q_i, w, k_i), -jnp.inf),
                        cfg.index_topk)
                ctx = mla_attend(cfg, sizes, p["attn"], q_n, q_r, latent,
                                 k_r, selection, False)
            else:
                latent_pool_v = view.write(
                    latent_pool_v, layer,
                    mla_cache_row(cfg, sizes, latent, k_r))
                if full_layer is not None:
                    index_pool_v = view.write(index_pool_v, full_layer, k_i)
                    scores = walk.index_scores(
                        index_pool_v, full_layer, q_i, w)
                    if T == 1:
                        # The selection as pool cells, through the table
                        # once for every layer that shares it.
                        chosen = select_top(
                            scores[:, 0], min(cfg.index_topk, walk.span))
                        selection = (*walk.cells_of(chosen),
                                     chosen <= positions)
                    else:
                        selection = causal & select_mask(
                            scores, cfg.index_topk)
                if T == 1:
                    blocks, offsets, valid = selection
                    rows = latent_pool_v[layer, blocks, offsets]
                    ctx = mla_attend(
                        cfg, sizes, p["attn"], q_n, q_r, rows[..., :rank],
                        rows[..., rank:lw], valid[:, None, :], True)
                else:
                    ctx = walk.masked_attention(
                        cfg, sizes, p["attn"], latent_pool_v, layer, q_n,
                        q_r, selection)
            out = mla_output(cfg, sizes, p["attn"], xn, ctx)
            return x + out, (latent_pool_v, index_pool_v), selection

        full_layers, selection, count_rows = 0, None, []
        for layer, kind in enumerate(cfg.layer_kinds):
            mlp, indexer = kind.split("_")
            p = params[f"layer_{layer}"]
            full_layer = None
            if indexer == FULL:
                full_layer, full_layers = full_layers, full_layers + 1
            h, pools, selection = attention(
                p, x, pools, layer, full_layer, selection)
            # What the layer's attention read, for who asks (``mutable=
            # ["intermediates"]``; nothing in a served program): the mask
            # ``(B, T, context)``, or a decode step's pool cells.
            self.sow("intermediates", f"selection_{layer}", selection)
            hn = rms_norm(h, p["post_norm"]["scale"], cfg.rms_norm_eps)
            if mlp == DENSE:
                x = h + gated_mlp(p["mlp"], hn.astype(dt), dt)
            else:
                y, row = expert_layer(
                    cfg, p, hn.reshape(B * T, cfg.hidden_size), token_live,
                    mesh=self.mesh)
                x = h + y.reshape(h.shape)
                count_rows.append(row)
        view.close(*pools,
                   counts=jnp.stack(count_rows) if count_rows else None)
        return view.head(params, x)


# -- what the engine and the scheduler ask of a decoder family -----------------

def cache_geometry(cfg: GlmMoeDsaConfig, paged: PagedKVConfig
                   ) -> Dict[str, Any]:
    """Both pools.  A block of the table is one block of each, so
    ``block_bytes`` is what the allocator's one block holds;
    ``selected_positions`` is the most latent rows a layer's attention
    reads of a row (the scheduler counts ``decode_selected_positions`` by
    it)."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    latent = cfg.pool_width * itemsize
    index = cfg.index_head_dim * itemsize
    layers, fulls = cfg.num_hidden_layers, cfg.n_full_layers
    cells = paged.num_blocks * paged.block_size
    out = {
        "kind": "latent_indexed",
        "pools_per_layer": 1,
        "values_per_token_layer": cfg.latent_width,
        "pool_width": cfg.pool_width,
        "padding_values": cfg.pool_width - cfg.latent_width,
        "bytes_per_token_layer": latent,
        "index_layers": fulls,
        "index_values_per_token_layer": cfg.index_head_dim,
        "index_bytes_per_token_layer": index,
        "bytes_per_token": layers * latent + fulls * index,
        "selected_positions": cfg.index_topk,
        "latent_block_bytes": layers * paged.block_size * latent,
        "index_block_bytes": fulls * paged.block_size * index,
        "latent_pool_bytes": layers * cells * latent,
        "index_pool_bytes": fulls * cells * index,
    }
    out["block_bytes"] = out["latent_block_bytes"] + out["index_block_bytes"]
    out["pool_bytes"] = out["latent_pool_bytes"] + out["index_pool_bytes"]
    return out


SERVE_REFUSALS = serve_refusals(
    "the latent and index-key pools",
    kv_dtype=(
        "both pools are stored in the compute type: the published float8 "
        "index keys need a scale a block and a dequantizing score, an int8 "
        "latent its own scale layout"),
    spec_k=(
        "a verify launch is k+1 queries a row, each with its own "
        "selection, and rolls rejected positions back in both pools: not "
        "built or tested (nor is a drafter from the model's own "
        "multi-token-prediction layer)"),
    prefix_cache=(
        "a shared prefix block would share its index keys too, and a "
        "suffix prefill would have to select over them: not tested yet"))


def make_workload(*, preset: str = "published",
                  config: Optional[GlmMoeDsaConfig] = None,
                  mesh: Optional[Mesh] = None, **kw) -> Workload:
    cfg = config or getattr(GlmMoeDsaConfig, preset)()
    return decoder_workload("glm_moe_dsa", GlmMoeDsa, cfg, mesh,
                            cache_geometry, SERVE_REFUSALS, **kw)
