"""A grouped-query decoder whose layers are window and full attention by
turns, every MLP a layer of sparse experts (``model_type`` ``mellum``), for
the serving path.

The third decoder family.  RMS norm, rotate-half rotary positions, the
stacked parameter leaves, the float32 router, the expert layer that is told
which experts it holds and the grouped-query attention itself are
``models/decoder_parts.py``'s; the write and the read of a pool row, by the
block-table kernel or the gather, are ``models/paged_call.py``'s.  What is
this family's own:

- **Grouped-query attention.**  ``num_attention_heads`` query heads over
  ``num_key_value_heads`` K/V heads of ``head_dim``: K/V head ``g`` serves
  query heads ``g * G .. g * G + G - 1`` (``G`` their ratio).  The cache
  holds K and V at the K/V head count, side by side in one row of
  ``2 * num_key_value_heads * head_dim`` values a token and layer (whole
  128-lane tiles at the published sizes, no padding).
- **Two kinds of layer, two kinds of cache.**  ``layer_types[l]`` is
  ``sliding_attention`` (a query at position ``i`` reads keys ``j <= i``
  with ``i - j < sliding_window``: the window counts the query itself) or
  ``full_attention`` (causal).  The scanned stack's body is one period of
  the pattern (three window layers and one full, as published).  Full
  layers keep their K/V in a paged pool ``(full layers, num_blocks,
  block_size, row)`` addressed through the slot's block-table row, as the
  other families do.  Window layers keep theirs in a second pool
  ``(window layers, window_blocks, block_size, row)`` in which a slot owns
  a RING of ``window_ring`` blocks whatever its length
  (``PagedKVConfig``): position ``p`` is written to ring cell ``p %
  (window_ring * block_size)``, over whatever slid out of the window, and
  a gathering call reads the whole ring back with each cell's position
  worked out from the row's length.  A call of ``T`` positions needs the
  ``sliding_window - 1`` before its first still in the ring, so ``T +
  sliding_window - 1`` may not pass the ring's capacity (refused at trace
  time).
- **Two rotary tables.**  Window layers rotate by the plain table
  ``rope_theta ** (-2i / head_dim)``; full layers by YaRN's
  (``yarn_inv_freq``), with ``attention_factor`` on cos and sin.
- **A softmax router**: softmax over all ``num_experts`` in float32, the
  top ``num_experts_per_tok`` by score, their weights renormalized; no
  bias, no scale, no shared expert (``route``'s ``"softmax"`` kind).

A decode step (one query position a row) reads both pools where they lie,
through the block-table kernel (``ops/paged_attention.py``: a full layer
the row's own blocks, a window layer the ring's blocks from the window's
first position on; ``KERNEL_WINDOW`` / ``KERNEL_FULL`` in
``attention_paths()``) wherever ``paged_attention.supported`` says it runs.
Every other call (a prefill chunk, the CPU without the interpreter) gathers
its pool rows and attends densely under the mask (``GATHER_WINDOW`` /
``GATHER_FULL``), which is also what the kernel is tested against.

Precision as ``decoder_parts`` has it: parameters and products' operands in
``dtype`` (bfloat16), float32 accumulation; the residual stream, norms,
rotations, router, softmax and logits float32.  The multi-token head the
family's description mentions has no key in ``config.json`` and the main
model's logits do not depend on it: not built.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import lax
from jax.sharding import Mesh

from distributed_tensorflow_tpu.models import PagedKVConfig, Workload
from distributed_tensorflow_tpu.models.decoder_parts import (
    GATHER_FULL, GATHER_WINDOW, KERNEL_FULL, KERNEL_WINDOW, attention_mask,
    check_share, declare, dot, expert_layer, layer_leaves, mlp_spec,
    rms_norm, rope, stacked)
from distributed_tensorflow_tpu.models.paged_call import (
    PagedCall, decoder_workload, serve_refusals)

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """Published keys of ``config.json`` under their own names (the nested
    ``rope_parameters`` group may be given whole: its numbers land in the
    flat fields), plus the share of the expert layer this device holds."""

    vocab_size: int = 98304
    hidden_size: int = 2304
    moe_intermediate_size: int = 896
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64                   # the router's width, as published
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    sliding_window: int = 1024
    # None: ``full_attention`` where ``l % 4 == 3``, as published.  A longer
    # list than ``num_hidden_layers`` is cut to it (a cut in depth keeps the
    # published list).
    layer_types: Optional[Tuple[str, ...]] = None
    rope_theta: float = 500000.0
    # YaRN, full layers only (``rope_parameters["full_attention"]``).
    rope_factor: float = 16.0
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.2772588722239782
    max_position_embeddings: int = 131072
    rope_parameters: Any = None             # the published group; consumed
    # This device's share: ``experts_held`` consecutive experts starting at
    # ``first_expert``.  None holds them all (the uncut layer).
    experts_held: Optional[int] = None
    first_expert: int = 0
    dtype: Any = jnp.bfloat16               # products' operands, parameters

    router = "softmax"                      # ``decoder_parts.route``'s kind

    def __post_init__(self):
        put = lambda name, value: object.__setattr__(self, name, value)
        if self.rope_parameters is not None:
            full = dict(self.rope_parameters[FULL])
            plain = dict(self.rope_parameters[SLIDING])
            if (full.get("rope_type") != "yarn"
                    or plain.get("rope_type") != "default"
                    or full["rope_theta"] != plain["rope_theta"]):
                raise ValueError(
                    "rope_parameters: full layers 'yarn' and window layers "
                    "'default' over one rope_theta, got "
                    f"{self.rope_parameters}")
            put("rope_theta", float(full["rope_theta"]))
            put("rope_factor", float(full["factor"]))
            put("original_max_position_embeddings",
                int(full["original_max_position_embeddings"]))
            put("beta_fast", float(full["beta_fast"]))
            put("beta_slow", float(full["beta_slow"]))
            put("attention_factor", float(full["attention_factor"]))
            put("rope_parameters", None)
        types = self.layer_types
        if types is None:
            types = tuple(FULL if l % 4 == 3 else SLIDING
                          for l in range(self.num_hidden_layers))
        types = tuple(types)[:self.num_hidden_layers]
        if (len(types) != self.num_hidden_layers
                or set(types) - {SLIDING, FULL}):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {SLIDING!r} or {FULL!r}, got {types}")
        put("layer_types", types)
        check_share(self, self.num_experts, "num_experts")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_attention_heads {self.num_attention_heads} must be a "
                f"multiple of num_key_value_heads {self.num_key_value_heads}")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even (rotary pairs)")
        if self.sliding_window < 1:
            raise ValueError("sliding_window must be >= 1")

    @property
    def held(self) -> int:
        return (self.num_experts if self.experts_held is None
                else int(self.experts_held))

    @property
    def period(self) -> int:
        """Layers in the scanned body: the shortest pattern ``layer_types``
        repeats (all of them where it repeats none)."""
        n, types = self.num_hidden_layers, self.layer_types
        return next(p for p in range(1, n + 1)
                    if n % p == 0 and types == types[:p] * (n // p))

    @property
    def n_window_layers(self) -> int:
        return sum(t == SLIDING for t in self.layer_types)

    @property
    def n_full_layers(self) -> int:
        return self.num_hidden_layers - self.n_window_layers

    @property
    def kv_row(self) -> int:
        """Values cached a token and layer: K and V of every K/V head."""
        return 2 * self.num_key_value_heads * self.head_dim

    @property
    def n_positions(self) -> int:
        """What the engine checks a slot's length against."""
        return self.max_position_embeddings

    @classmethod
    def published(cls, **kw):
        """Mellum2-12B-A2.5B-Instruct's sizes, every expert held."""
        return cls(**kw)

    @classmethod
    def v5e4_share(cls, **kw):
        """One chip's share of a v5e-4 host on which 4 chips share each
        layer (16 experts a layer, 1/4 of the vocabulary's rows), at the
        depth one chip serves beside its float32 reference (four whole
        periods): the sizes of ``benchmark/configs/mellum2-12b-a2.5b.json``."""
        base = dict(num_hidden_layers=16, vocab_size=24576, experts_held=16)
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny(cls, **kw):  # tests
        base = dict(
            vocab_size=256, hidden_size=64, moe_intermediate_size=32,
            num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_experts=8,
            num_experts_per_tok=2, sliding_window=24,
            original_max_position_embeddings=32,
            max_position_embeddings=512)
        base.update(kw)
        return cls(**base)


def yarn_inv_freq(cfg: MellumConfig) -> np.ndarray:
    """The full layers' rotary table ``(head_dim / 2,)``: pair ``i``'s plain
    frequency ``theta ** (-2i / D)`` where it turns more than ``beta_fast``
    times inside the original context, that frequency over ``rope_factor``
    where it turns fewer than ``beta_slow`` times, and a linear ramp
    between the two pairs those counts fall on."""
    dim, half = cfg.head_dim, cfg.head_dim // 2
    extra = cfg.rope_theta ** (-2.0 * np.arange(half) / dim)
    inter = extra / cfg.rope_factor

    def pair_of(turns):
        return (dim * math.log(cfg.original_max_position_embeddings
                               / (turns * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(pair_of(cfg.beta_fast)), 0)
    high = min(math.ceil(pair_of(cfg.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def plain_inv_freq(cfg: MellumConfig) -> np.ndarray:
    half = cfg.head_dim // 2
    return (cfg.rope_theta ** (-2.0 * np.arange(half) / cfg.head_dim)
            ).astype(np.float32)


# -- parameters ----------------------------------------------------------------

def _layer_spec(cfg):
    d, hd = cfg.hidden_size, cfg.head_dim
    return (
        ("input_norm", (("scale", (d,)),)),
        ("attn", (
            ("q", (("kernel", (d, cfg.num_attention_heads * hd)),)),
            ("k", (("kernel", (d, cfg.num_key_value_heads * hd)),)),
            ("v", (("kernel", (d, cfg.num_key_value_heads * hd)),)),
            ("o", (("kernel", (cfg.num_attention_heads * hd, d)),)),
        )),
        ("post_norm", (("scale", (d,)),)),
        ("router", (("kernel", (d, cfg.num_experts)),)),
        ("experts", mlp_spec(d, cfg.moe_intermediate_size,
                             lead=(cfg.held,))),
    )


def param_spec(cfg):
    d = cfg.hidden_size
    return (
        ("embed", (cfg.vocab_size, d)),
        ("layers", stacked(_layer_spec(cfg), cfg.num_hidden_layers)),
        ("final_norm", (("scale", (d,)),)),
        ("head", (("kernel", (d, cfg.vocab_size)),)),
    )


# -- the layer's mathematics ---------------------------------------------------

def gqa_project(cfg, p, xn, positions, full: bool):
    """``xn`` (normalized, compute type) -> rotated ``q`` ``(B, T, Hkv, G,
    D)`` and ``k`` ``(B, T, Hkv, D)``, and ``v``, each rounded once."""
    B, T, _ = xn.shape
    hkv, hd = cfg.num_key_value_heads, cfg.head_dim
    g = cfg.num_attention_heads // hkv
    q = dot("btd,df->btf", xn, p["q"]["kernel"]).reshape(B, T, hkv, g, hd)
    k = dot("btd,df->btf", xn, p["k"]["kernel"]).reshape(B, T, hkv, hd)
    v = dot("btd,df->btf", xn, p["v"]["kernel"], cfg.dtype).reshape(
        B, T, hkv, hd)
    table = dict(inv_freq=yarn_inv_freq(cfg), scale=cfg.attention_factor) \
        if full else dict(inv_freq=plain_inv_freq(cfg))
    q = rope(q, positions, cfg.rope_theta, **table).astype(cfg.dtype)
    k = rope(k, positions, cfg.rope_theta, **table).astype(cfg.dtype)
    return q, k, v


# -- the module ----------------------------------------------------------------

class Mellum(nn.Module):
    cfg: MellumConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, *, deterministic: bool = True,
                 decode: bool = False, slot_ids=None,
                 paged: Optional[PagedKVConfig] = None, block_tables=None,
                 live=None):
        cfg = self.cfg
        B, T = tokens.shape
        params = declare(self, param_spec(cfg), cfg)
        # Float32 from here to the head: the router reads the stream's norm
        # unrounded.
        x = params["embed"][tokens].astype(jnp.float32)
        view = PagedCall(
            self, B, T, decode=decode, slot_ids=slot_ids, paged=paged,
            block_tables=block_tables, live=live, pools="the two K/V pools",
            refusals=SERVE_REFUSALS, experts=(cfg.num_hidden_layers, cfg.held))
        n_win = cfg.n_window_layers
        positions = view.positions
        # Full layers: the whole table row is read back, in position order.
        full_mask = attention_mask(positions, view.key_positions(), None)
        win_cells = win_blocks = None
        if decode:
            if n_win and not paged.window_ring:
                raise ValueError(
                    "window layers need the window pool: paged.window_ring "
                    "and paged.window_blocks (PagedKVConfig), which the "
                    "continuous scheduler sizes from cache_geometry()")
            # A ring as long as the row's own table never wraps; the
            # engine's shape-only init call is as long as the row.
            wraps = (0 < paged.window_ring
                     < block_tables.shape[1] - paged.window_ring)
            if (wraps and not self.is_initializing()
                    and T + cfg.sliding_window - 1 > paged.window_capacity):
                raise ValueError(
                    f"a call of {T} positions needs the {cfg.sliding_window}"
                    f" - 1 before its first still in the window ring: "
                    f"{T + cfg.sliding_window - 1} positions, and the ring "
                    f"holds {paged.window_capacity} ({paged.window_ring} "
                    f"blocks of {paged.block_size}); prefill in chunks "
                    f"(prefill_budget) or size the ring for the call")
            bs, win_blocks = paged.block_size, max(paged.window_blocks, 1)
            win_mask = None
            if n_win:
                # Window layers: position p in ring cell p % cap.  After
                # this call's writes, cell c holds the newest position
                # <= last with that remainder (below 0: never written).
                cap = paged.window_capacity
                ring_pos = positions % cap
                win_cells = (jnp.take_along_axis(
                    view.ring, ring_pos // bs, axis=1).reshape(-1),
                    (ring_pos % bs).reshape(-1))
                last = positions[:, -1:]                          # (B, 1)
                held_pos = last - (last - jnp.arange(cap)[None]) % cap
                win_mask = attention_mask(positions, held_pos,
                                          cfg.sliding_window)
        else:
            win_mask = attention_mask(positions, positions,
                                      cfg.sliding_window)
        view.advance()
        pools = (
            view.pool("window_pool", n_win, cfg.kv_row, cfg.dtype,
                      blocks=win_blocks),
            view.pool("full_pool", cfg.n_full_layers, cfg.kv_row, cfg.dtype))
        lengths, token_live = view.lengths, view.token_live
        # A full layer reads the row's own blocks; a window layer its
        # ring's, from the window's first position on.
        reads = {
            True: dict(mask=full_mask, paths=(GATHER_FULL, KERNEL_FULL)),
            False: dict(mask=win_mask, paths=(GATHER_WINDOW, KERNEL_WINDOW),
                        table=view.ring, cells=win_cells,
                        window=cfg.sliding_window)}

        def attention(p, x, pool, layer, full: bool):
            xn = rms_norm(x, p["input_norm"]["scale"],
                          cfg.rms_norm_eps).astype(cfg.dtype)
            q, k, v = gqa_project(cfg, p["attn"], xn, positions, full)
            ctx, pool = view.gqa(pool, layer, q, k, v, lengths=lengths,
                                 **reads[full])
            return x + dot("btf,fd->btd", ctx, p["attn"]["o"]["kernel"]), pool

        period = cfg.period
        kinds = [t == FULL for t in cfg.layer_types[:period]]
        win_per = period - sum(kinds)

        def one_period(carry, n):
            x, win_pool, full_pool = carry
            rows = []
            seen = [0, 0]                   # window, full layers so far
            for i, full in enumerate(kinds):
                p = layer_leaves(params["layers"], n * period + i)
                if full:
                    layer = n * (period - win_per) + seen[1]
                    h, full_pool = attention(p, x, full_pool, layer, True)
                else:
                    layer = n * win_per + seen[0]
                    h, win_pool = attention(p, x, win_pool, layer, False)
                seen[full] += 1
                hn = rms_norm(h, p["post_norm"]["scale"], cfg.rms_norm_eps)
                y, row = expert_layer(
                    cfg, dict(p, experts=params["layers"]["experts"]),
                    hn.reshape(B * T, cfg.hidden_size), token_live,
                    layer=n * period + i, mesh=self.mesh)
                x = h + y.reshape(h.shape)
                rows.append(row)
            return (x, win_pool, full_pool), jnp.stack(rows)

        (x, *pools), rows = lax.scan(
            one_period, (x,) + pools,
            jnp.arange(cfg.num_hidden_layers // period, dtype=jnp.int32))
        view.close(*pools, counts=rows)
        return view.head(params, x)


# -- what the engine and the scheduler ask of a decoder family -----------------

def cache_geometry(cfg: MellumConfig, paged: PagedKVConfig) -> Dict[str, Any]:
    """Both kinds of pool.  ``window_positions`` and the layer counts are
    what the scheduler sizes the ring from before a ``paged`` with a window
    pool exists; the ``window`` group is what that pool then costs."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    per_layer = cfg.kv_row * itemsize
    block = paged.block_size * per_layer
    out = {
        "kind": "key_value_grouped",
        "pools_per_layer": 1,
        "values_per_token_layer": cfg.kv_row,
        "pool_width": cfg.kv_row,
        "padding_values": 0,
        "bytes_per_token_layer": per_layer,
        # A token's bytes while it is inside the window, and after.
        "bytes_per_token": cfg.num_hidden_layers * per_layer,
        "bytes_per_token_past_window": cfg.n_full_layers * per_layer,
        "full_layers": cfg.n_full_layers,
        "window_layers": cfg.n_window_layers,
        "window_positions": cfg.sliding_window if cfg.n_window_layers else 0,
        "full_block_bytes": cfg.n_full_layers * block,
        "window_block_bytes": cfg.n_window_layers * block,
        "full_pool_bytes": cfg.n_full_layers * paged.num_blocks * block,
        "window_ring_blocks": paged.window_ring,
        "window_ring_positions": paged.window_capacity,
        "window_pool_bytes": (cfg.n_window_layers * paged.window_blocks
                              * block),
    }
    out["pool_bytes"] = out["full_pool_bytes"] + out["window_pool_bytes"]
    return out


SERVE_REFUSALS = serve_refusals(
    "the two K/V pools",
    slo_scheduling=(
        "host tiering swaps one pool's blocks and does not know the window "
        "ring, whose blocks hold a row's latest positions and not its "
        "first; preempting would lose a victim's cache"),
    spec_k=(
        "a verify launch rolls rejected positions back, and in the ring "
        "they have already overwritten the positions a window behind"),
    prefix_cache=(
        "a shared prefix block of a window layer may already be "
        "overwritten by the request that registered it"))


def make_workload(*, preset: str = "published",
                  config: Optional[MellumConfig] = None,
                  mesh: Optional[Mesh] = None, **kw) -> Workload:
    cfg = config or getattr(MellumConfig, preset)()
    return decoder_workload("mellum", Mellum, cfg, mesh, cache_geometry,
                            SERVE_REFUSALS, **kw)
