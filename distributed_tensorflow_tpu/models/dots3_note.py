"""A latent-attention, sparse-expert decoder whose layers are of two kinds
with their own sizes: full layers that read what a learned indexer selects,
and window layers whose latent rows live in a ring (``model_type``
``dots3_note``), for the serving path.

The fifth paged-only decoder family (the sixth served).  RMS norm, rotary positions, the parameters'
declaration, the latent projections and both forms of latent attention
(``mla_*``, by a kind's ``MlaSizes``), the indexer and its selection, the
float32 sigmoid router and the expert layer that is told which experts it
holds are ``models/decoder_parts.py``'s; a position's pool cell, the row
write and the walk over a long context are ``models/paged_call.py``'s.  What
is this family's own is how the two kinds lie side by side:

    h = x + Attn_l(RMSNorm(x));  y = h + FFN_l(RMSNorm(h));  final RMSNorm;
    untied head.

``layer_types[l]`` is ``full_attention`` or ``sliding_attention``
(published: layers 0 and 1 full, then (sliding, sliding, sliding, full) x
11).  Sizes by kind (``Dots3NoteConfig.full`` / ``.sliding``):

    kind     heads  r_q   r_kv  d_n  d_r  d_v  theta  reads
    full     128    1024  512   128  64   128  8e7    S_t, the selection
    sliding  64     1024  1024  192  64   128  5e4    s <= t, t - s < 513

- **Latent attention, either kind** (``xn = RMSNorm(x)``).  ``c_q = a_q *
  RMSNorm(xn W_qa)``, ``a_q = sqrt(d / r_q)``; ``q = c_q W_qb`` -> heads of
  ``[q_n | q_r]``, ``q_r`` rotated.  ``[c | k_r] = xn W_kva``; ``c_kv =
  a_kv * RMSNorm(c)``, ``a_kv = sqrt(d / r_kv)``; ``k_r`` rotated, one for
  all heads; ``[k_n | v] = c_kv W_kvb`` a head.  Scores ``(q_n . k_n + q_r .
  k_r) / sqrt(d_n + d_r)``, softmax over the positions the kind reads.
  **Gate**: ``g = sigmoid(xn W_g)``, ``W_g`` ``(d, H)``, one scalar a head
  and position; the output is ``concat_h(g_h * o_h) W_o``.  Cached: ``c_kv``
  after ``a_kv`` (scaled in float32 with the norm, rounded once) and the
  rotated ``k_r``: 576 values a position on a full layer, 1,088 on a window
  layer.
- **The indexer**, on every full layer and on no other; no layer shares
  another's: from the layer's (scaled) ``c_q`` and ``xn``, the first
  ``qk_rope_head_dim`` dimensions rotated by the full kind's ``theta``;
  ``S_t`` is the ``min(t + 1, index_topk)`` positions of largest score, the
  lower position first on a tie.
- **FFN.**  The first ``first_k_dense_replace`` layers a gated MLP; the
  rest sigmoid-routed experts (``route``'s ``sigmoid_bias``) and one shared
  expert.

**Three pools under one table row** (``PagedCall.table`` | ``.ring``).  On
the full layers the latent pool ``(full layers, num_blocks, block_size,
640)`` and the index keys ``(full layers, num_blocks, block_size, 128)``,
block ``b`` of the one being block ``b`` of the other.  On the window
layers a ring pool ``(window layers, window_blocks, block_size, 1152)``:
position ``p`` in ring cell ``p % capacity``, over whatever slid out of the
window, so a call of ``T`` positions needs the ``sliding_window_size - 1``
before its first still in the ring (refused at trace time otherwise).

A cached call runs, on record (``ops.paged_attention.note_path``): full
layers ``SELECTED`` (a decode step: scores over the row's index keys, top-k,
a gather of the selected latent rows, absorbed) or ``MASKED`` (a prefill
chunk: expanded under the selection's mask, context chunk by chunk); window
layers ``WINDOW_STEP`` (a decode step gathers the ring blocks that hold the
window, ``sliding_window_size`` live cells a row at most, and attends
absorbed) or ``WINDOW_CHUNK`` (a prefill chunk gathers the blocks of its own
positions and the window before them and attends expanded under the mask).
All four are gathers; a block-walking kernel for latent rows is ROADMAP's.

Precision as ``decoder_parts`` has it.  Not built: the vision and audio
towers, the multi-token-prediction layer (no key in ``config.json``), float8
index keys.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import Mesh

from distributed_tensorflow_tpu.models import PagedKVConfig, Workload
from distributed_tensorflow_tpu.models.decoder_parts import (
    MlaSizes, attention_mask, check_share, declare, expert_layer, gated_mlp,
    index_scores, indexer_project, indexer_spec, mla_attend, mla_cache_row,
    mla_output, mla_project, mla_query_latent, mla_spec, mlp_spec, rms_norm,
    select_mask, select_top)
from distributed_tensorflow_tpu.models.paged_call import (
    ContextWalk, PagedCall, decoder_workload, serve_refusals)
from distributed_tensorflow_tpu.ops import paged_attention

# The cached attention's four implementations, as ``attention_paths()``
# names them.
SELECTED, MASKED = "latent_sparse_selected", "latent_sparse_masked"
WINDOW_STEP, WINDOW_CHUNK = "latent_window_step", "latent_window_chunk"

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
HEADWISE = "headwise"
_KIND = {FULL: "full", SLIDING: "sliding"}


@dataclasses.dataclass(frozen=True)
class Dots3NoteConfig:
    """Published keys of ``config.json`` under their own names, plus the
    share of the expert layer this device holds."""

    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824          # a dense layer's MLP
    moe_intermediate_size: int = 1536       # one expert, routed or shared
    num_hidden_layers: int = 46
    first_k_dense_replace: int = 1
    # None: full on layers 0 and 1 and then on every fourth layer
    # (``sliding, sliding, sliding, full``), as published.  A longer list
    # than ``num_hidden_layers`` is cut to it.
    layer_types: Optional[Tuple[str, ...]] = None
    # The full layers' latent attention.
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    attention_gate_type: Optional[str] = HEADWISE
    # The window layers'.
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    swa_attention_gate_type: Optional[str] = HEADWISE
    sliding_window_size: int = 513          # the query's own place counted
    apply_mla_qkv_lora_rescale: bool = True
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    n_routed_experts: int = 256             # the router's width, as published
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6            # the index key's layer norm
    max_position_embeddings: int = 524288
    # This device's share: ``experts_held`` consecutive experts starting at
    # ``first_expert``.  None holds them all (the uncut layer).
    experts_held: Optional[int] = None
    first_expert: int = 0
    dtype: Any = jnp.bfloat16               # products' operands, parameters

    router = "sigmoid_bias"                 # ``decoder_parts.route``'s kind

    def __post_init__(self):
        n = self.num_hidden_layers
        types = self.layer_types
        if types is None:
            types = tuple(FULL if l < 2 or l % 4 == 1 else SLIDING
                          for l in range(n))
        types = tuple(types)[:n]
        if len(types) != n or set(types) - {FULL, SLIDING}:
            raise ValueError(
                f"layer_types must name {n} layers, each {FULL!r} or "
                f"{SLIDING!r}, got {types}")
        object.__setattr__(self, "layer_types", types)
        check_share(self, self.n_routed_experts, "n_routed_experts")
        if not 0 <= self.first_k_dense_replace <= n:
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} must "
                f"lie in 0..num_hidden_layers {n}")
        for gate in (self.attention_gate_type, self.swa_attention_gate_type):
            if gate not in (None, HEADWISE):
                raise ValueError(
                    f"attention gate type {gate!r}: only {HEADWISE!r} (one "
                    "sigmoid gate a head) or None is built")
        if self.qk_rope_head_dim % 2 or self.swa_qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotary pairs)")
        if self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError(
                f"index_head_dim {self.index_head_dim} must hold the "
                f"{self.qk_rope_head_dim} rotated dimensions")
        if self.index_topk < 1:
            raise ValueError("index_topk must be >= 1")
        if self.sliding_window_size < 1:
            raise ValueError("sliding_window_size must be >= 1")

    def _scale(self, rank: int) -> float:
        """What brings a rank-``rank`` normalized latent back to the
        width's scale (``apply_mla_qkv_lora_rescale``)."""
        return (math.sqrt(self.hidden_size / rank)
                if self.apply_mla_qkv_lora_rescale else 1.0)

    @property
    def full(self) -> MlaSizes:
        return MlaSizes(
            heads=self.num_attention_heads, q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
            q_scale=self._scale(self.q_lora_rank),
            kv_scale=self._scale(self.kv_lora_rank),
            gated=self.attention_gate_type == HEADWISE)

    @property
    def sliding(self) -> MlaSizes:
        return MlaSizes(
            heads=self.swa_num_attention_heads,
            q_lora_rank=self.swa_q_lora_rank,
            kv_lora_rank=self.swa_kv_lora_rank,
            qk_nope_head_dim=self.swa_qk_nope_head_dim,
            qk_rope_head_dim=self.swa_qk_rope_head_dim,
            v_head_dim=self.swa_v_head_dim, rope_theta=self.swa_rope_theta,
            q_scale=self._scale(self.swa_q_lora_rank),
            kv_scale=self._scale(self.swa_kv_lora_rank),
            gated=self.swa_attention_gate_type == HEADWISE)

    @property
    def held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else int(self.experts_held))

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's stack: its MLP's kind and its attention's
        (``dense_full``, ``sparse_full``, ``sparse_sliding``)."""
        return tuple(
            f"{DENSE if l < self.first_k_dense_replace else SPARSE}_"
            f"{_KIND[t]}" for l, t in enumerate(self.layer_types))

    @property
    def n_full_layers(self) -> int:
        return sum(t == FULL for t in self.layer_types)

    @property
    def n_window_layers(self) -> int:
        return self.num_hidden_layers - self.n_full_layers

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def n_positions(self) -> int:
        """What the engine checks a slot's length against."""
        return self.max_position_embeddings

    @classmethod
    def published(cls, **kw):
        """dots3-note-prev's sizes, every expert held."""
        return cls(**kw)

    @classmethod
    def v5e256_share(cls, **kw):
        """One chip's share of a v5e-256 on which 32 chips share each layer
        (8 experts a layer, 1/8 of the vocabulary's rows), at the depth one
        chip serves beside its float32 reference: published layers 0-4 (the
        dense layer and one whole period: a full layer and three window
        layers), the sizes of ``benchmark/configs/dots3-note-prev.json``."""
        base = dict(num_hidden_layers=5, vocab_size=19008, experts_held=8)
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny(cls, **kw):  # tests
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=5,
            num_attention_heads=4, q_lora_rank=32, kv_lora_rank=48,
            qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=32,
            swa_num_attention_heads=2, swa_q_lora_rank=32,
            swa_kv_lora_rank=64, swa_qk_nope_head_dim=24,
            swa_qk_rope_head_dim=16, swa_v_head_dim=32,
            sliding_window_size=25, index_n_heads=4, index_head_dim=32,
            index_topk=24, n_routed_experts=8, num_experts_per_tok=2,
            max_position_embeddings=512)
        base.update(kw)
        return cls(**base)


# -- parameters ----------------------------------------------------------------

def _layer_spec(cfg, kind: str):
    mlp, attn = kind.split("_")
    d, sizes = cfg.hidden_size, getattr(cfg, attn)
    spec = (("input_norm", (("scale", (d,)),)),
            ("attn", mla_spec(cfg, sizes)))
    if attn == "full":
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

class Dots3Note(nn.Module):
    cfg: Dots3NoteConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, *, deterministic: bool = True,
                 decode: bool = False, slot_ids=None,
                 paged: Optional[PagedKVConfig] = None, block_tables=None,
                 live=None):
        cfg = self.cfg
        B, T = tokens.shape
        params = declare(self, param_spec(cfg), cfg)
        dt, window = cfg.dtype, cfg.sliding_window_size
        n_win = cfg.n_window_layers
        # Float32 from here to the head.
        x = params["embed"][tokens].astype(jnp.float32)
        view = PagedCall(
            self, B, T, decode=decode, slot_ids=slot_ids, paged=paged,
            block_tables=block_tables, live=live,
            pools="the latent, index-key and window-latent pools",
            refusals=SERVE_REFUSALS, experts=(cfg.n_moe_layers, cfg.held))
        positions = view.positions
        win_cells = win_blocks = win_read = None
        if decode:
            walk = ContextWalk(view)
            causal = walk.causal
            if cfg.n_full_layers:
                paged_attention.note_path(SELECTED if T == 1 else MASKED)
            if n_win:
                if not paged.window_ring:
                    raise ValueError(
                        "window layers need the window pool: "
                        "paged.window_ring and paged.window_blocks "
                        "(PagedKVConfig), which the continuous scheduler "
                        "sizes from cache_geometry()")
                # A ring as long as the row's own table never wraps; the
                # engine's shape-only init call is as long as the row.
                wraps = (paged.window_ring
                         < block_tables.shape[1] - paged.window_ring)
                if (wraps and not self.is_initializing()
                        and T + window - 1 > paged.window_capacity):
                    raise ValueError(
                        f"a call of {T} positions needs the {window} - 1 "
                        f"before its first still in the window ring: "
                        f"{T + window - 1} positions, and the ring holds "
                        f"{paged.window_capacity} ({paged.window_ring} "
                        f"blocks of {paged.block_size}); prefill in chunks "
                        f"(prefill_budget) or size the ring for the call")
                paged_attention.note_path(
                    WINDOW_STEP if T == 1 else WINDOW_CHUNK)
                bs, ring = paged.block_size, paged.window_ring
                win_blocks = paged.window_blocks
                # Position p in ring cell p % capacity.
                ring_pos = positions % paged.window_capacity
                win_cells = (jnp.take_along_axis(
                    view.ring, ring_pos // bs, axis=1).reshape(-1),
                    (ring_pos % bs).reshape(-1))
                # What the call reads back: the ring blocks that hold its
                # own positions and the window before its first, in
                # position order.  A cell holds the newest position with
                # its remainder, which for every position in a query's
                # window is that position; the mask hides the rest.
                count = min(-(-(T + window - 1) // bs) + 1, ring)
                first = jnp.maximum(view.start - (window - 1), 0) // bs
                order = first[:, None] + jnp.arange(count)[None, :]
                win_read = (
                    jnp.take_along_axis(view.ring, order % ring, axis=1),
                    attention_mask(
                        positions,
                        (order[:, :, None] * bs + jnp.arange(bs)
                         ).reshape(B, count * bs), window))
        else:
            causal = jnp.broadcast_to(
                jnp.tril(jnp.ones((T, T), bool))[None], (B, T, T))
            win_mask = attention_mask(positions, positions, window)
        view.advance()
        full, slide = cfg.full, cfg.sliding
        pools = (
            view.pool("latent_pool", cfg.n_full_layers, full.pool_width, dt),
            view.pool("index_pool", cfg.n_full_layers, cfg.index_head_dim,
                      dt),
            view.pool("window_pool", n_win, slide.pool_width, dt,
                      blocks=win_blocks))
        token_live = view.token_live

        def full_attention(p, xn, pools, layer):
            """A full layer, the ``layer``-th of them: its own indexer's
            selection and nobody else's."""
            latent_pool, index_pool, window_pool = pools
            rank, lw = full.kv_lora_rank, full.latent_width
            cq = mla_query_latent(cfg, full, p["attn"], xn)
            q_n, q_r, latent, k_r = mla_project(
                cfg, full, p["attn"], xn, positions, cq=cq)
            q_i, k_i, w = indexer_project(
                cfg, full, p["indexer"], xn, cq, positions)
            if latent_pool is None:
                selection = causal & select_mask(jnp.where(
                    causal, index_scores(q_i, w, k_i), -jnp.inf),
                    cfg.index_topk)
                ctx = mla_attend(cfg, full, p["attn"], q_n, q_r, latent,
                                 k_r, selection, False)
                return ctx, pools, selection
            latent_pool = view.write(
                latent_pool, layer, mla_cache_row(cfg, full, latent, k_r))
            index_pool = view.write(index_pool, layer, k_i)
            scores = walk.index_scores(index_pool, layer, q_i, w)
            if T == 1:
                chosen = select_top(
                    scores[:, 0], min(cfg.index_topk, walk.span))
                blocks, offsets = walk.cells_of(chosen)
                valid = chosen <= positions
                selection = (blocks, offsets, valid)
                rows = latent_pool[layer, blocks, offsets]
                ctx = mla_attend(
                    cfg, full, p["attn"], q_n, q_r, rows[..., :rank],
                    rows[..., rank:lw], valid[:, None, :], True)
            else:
                selection = causal & select_mask(scores, cfg.index_topk)
                ctx = walk.masked_attention(
                    cfg, full, p["attn"], latent_pool, layer, q_n, q_r,
                    selection)
            return ctx, (latent_pool, index_pool, window_pool), selection

        def window_attention(p, xn, pools, layer):
            """A window layer, the ``layer``-th of them."""
            latent_pool, index_pool, window_pool = pools
            rank, lw = slide.kv_lora_rank, slide.latent_width
            q_n, q_r, latent, k_r = mla_project(
                cfg, slide, p["attn"], xn, positions)
            if window_pool is None:
                ctx = mla_attend(cfg, slide, p["attn"], q_n, q_r, latent,
                                 k_r, win_mask, False)
                return ctx, pools, win_mask
            window_pool = view.write(
                window_pool, layer, mla_cache_row(cfg, slide, latent, k_r),
                win_cells)
            blocks, mask = win_read
            rows = window_pool[layer, blocks].reshape(
                B, -1, slide.pool_width)
            ctx = mla_attend(cfg, slide, p["attn"], q_n, q_r,
                             rows[..., :rank], rows[..., rank:lw], mask,
                             T == 1)
            return ctx, (latent_pool, index_pool, window_pool), win_read

        seen = {"full": 0, "sliding": 0}
        count_rows = []
        for layer, kind in enumerate(cfg.layer_kinds):
            mlp, attn = kind.split("_")
            p = params[f"layer_{layer}"]
            xn = rms_norm(x, p["input_norm"]["scale"],
                          cfg.rms_norm_eps).astype(dt)
            attend = full_attention if attn == "full" else window_attention
            ctx, pools, read = attend(p, xn, pools, seen[attn])
            seen[attn] += 1
            # What the layer's attention read, for who asks (``mutable=
            # ["intermediates"]``; nothing in a served program).
            self.sow("intermediates", f"selection_{layer}", read)
            h = x + mla_output(cfg, getattr(cfg, attn), p["attn"], xn, ctx)
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

def cache_geometry(cfg: Dots3NoteConfig, paged: PagedKVConfig
                   ) -> Dict[str, Any]:
    """All three pools.  A block of the table is one block of the latent
    pool and one of the index keys (``full_block_bytes``: what the
    allocator's one block holds); a ring block is one of the window pool
    (``window_block_bytes``).  ``window_positions`` is what the scheduler
    sizes the ring from, ``selected_positions`` the most latent rows a full
    layer's attention reads of a row."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    latent = cfg.full.pool_width * itemsize
    index = cfg.index_head_dim * itemsize
    wlatent = cfg.sliding.pool_width * itemsize
    fulls, wins, bs = cfg.n_full_layers, cfg.n_window_layers, paged.block_size
    out = {
        "kind": "latent_indexed_window",
        "pools_per_layer": 1,
        "values_per_token_layer": cfg.full.latent_width,
        "pool_width": cfg.full.pool_width,
        "bytes_per_token_layer": latent,
        "window_values_per_token_layer": cfg.sliding.latent_width,
        "window_pool_width": cfg.sliding.pool_width,
        "window_bytes_per_token_layer": wlatent,
        "index_layers": fulls,
        "index_values_per_token_layer": cfg.index_head_dim,
        "index_bytes_per_token_layer": index,
        # A token's bytes while it is inside the window, and after.
        "bytes_per_token": fulls * (latent + index) + wins * wlatent,
        "bytes_per_token_past_window": fulls * (latent + index),
        "full_layers": fulls,
        "window_layers": wins,
        "window_positions": cfg.sliding_window_size if wins else 0,
        "selected_positions": cfg.index_topk,
        "latent_block_bytes": fulls * bs * latent,
        "index_block_bytes": fulls * bs * index,
        "window_block_bytes": wins * bs * wlatent,
        "latent_pool_bytes": fulls * paged.num_blocks * bs * latent,
        "index_pool_bytes": fulls * paged.num_blocks * bs * index,
        "window_ring_blocks": paged.window_ring,
        "window_ring_positions": paged.window_capacity,
        "window_pool_bytes": wins * paged.window_blocks * bs * wlatent,
    }
    out["full_block_bytes"] = (out["latent_block_bytes"]
                               + out["index_block_bytes"])
    out["full_pool_bytes"] = (out["latent_pool_bytes"]
                              + out["index_pool_bytes"])
    out["pool_bytes"] = out["full_pool_bytes"] + out["window_pool_bytes"]
    return out


SERVE_REFUSALS = serve_refusals(
    "the latent, index-key and window-latent pools",
    kv_dtype=(
        "the three pools are stored in the compute type: float8 index keys "
        "need a scale a block and a dequantizing score, an int8 latent its "
        "own scale layout"),
    slo_scheduling=(
        "host tiering swaps one pool's blocks and knows neither the index "
        "keys under the same table nor the window ring, whose blocks hold "
        "a row's latest positions and not its first; preempting would "
        "lose a victim's cache"),
    spec_k=(
        "a verify launch is k+1 queries a row, each with its own "
        "selection, and rolls rejected positions back, which in the ring "
        "have already overwritten the positions a window behind (nor is a "
        "drafter from the model's own multi-token-prediction layer built)"),
    prefix_cache=(
        "a shared prefix block of a window layer may already be "
        "overwritten by the request that registered it, and a shared "
        "block of a full layer shares its index keys too, over which a "
        "suffix prefill would have to select: not tested yet"))


def make_workload(*, preset: str = "published",
                  config: Optional[Dots3NoteConfig] = None,
                  mesh: Optional[Mesh] = None, **kw) -> Workload:
    cfg = config or getattr(Dots3NoteConfig, preset)()
    return decoder_workload("dots3_note", Dots3Note, cfg, mesh,
                            cache_geometry, SERVE_REFUSALS, **kw)
