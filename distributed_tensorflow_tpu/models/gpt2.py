"""GPT-2 — reference workload 5 (BASELINE.json: "GPT-2 medium — large
allreduce + gradient accumulation").

TPU-first design notes:

- One fused qkv projection (``c_attn``) and one fused MLP — big matmuls for
  the MXU, bf16 compute.
- Megatron-style tensor parallelism comes entirely from sharding rules
  (``transformer_rules``): column-parallel qkv/fc-in, row-parallel
  out-proj/fc-out.  No collective appears in model code; XLA derives the
  all-reduces from the shardings.
- Gradient accumulation is the reference's answer to GPT-2-medium memory
  (``grad_accum_steps=4`` default here), implemented as ``lax.scan`` in the
  compiled step — not a Python loop.
- Weight-tied LM head (logits = x @ wte.T), standard GPT-2.
- Attention is exact softmax attention via einsum; the long-context path
  (ring attention over the ``context`` axis, ``parallel.ring_attention``)
  activates whenever the mesh's ``context`` axis has size > 1.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from flax import linen as nn
from jax.sharding import Mesh

from distributed_tensorflow_tpu.data.pipeline import synthetic_lm
from distributed_tensorflow_tpu.ops import flash_attention, paged_attention
from distributed_tensorflow_tpu.parallel.ring_attention import ring_attention
from distributed_tensorflow_tpu.models import (  # noqa: F401
    PagedKVConfig,  # defined beside Workload; re-exported for its callers
    Workload,
)
from distributed_tensorflow_tpu.ops.flash_attention import REMAT_POLICY
from distributed_tensorflow_tpu.parallel.sharding import (
    P, ShardingRules, _path_str,
    transformer_rules,
)

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    d_model: int = 1024
    n_layer: int = 24
    n_head: int = 16
    dropout: float = 0.1
    dtype: Any = jnp.bfloat16
    # Stack the transformer body as ONE scanned layer (lax.scan over stacked
    # params): O(1) compile time in depth, the canonical TPU structure.
    scan_layers: bool = True
    # Unroll factor for the layer scan (nn.scan unroll): >1 trades compile
    # time for fewer loop iterations, letting XLA fuse the per-layer grad
    # writes into the stacked (L, ...) buffers across unrolled layers —
    # attacks the dynamic-update-slice grad-stacking overhead (a sizeable
    # share of GPT-2 step time at unroll=1 in profiles that predate the
    # current chip attachment).
    scan_unroll: int = 1
    # Rematerialize each block in backward (jax.checkpoint), all of it but
    # the flash kernel's output and log-sum-exp (ops.flash_attention's
    # REMAT_POLICY): the kernel is the dearest part per byte to run twice.
    remat: bool = True
    # Pallas fused attention (ops.flash_attention).  Attention-prob dropout
    # runs in-kernel (TPU PRNG), matching the dense path's recipe.
    use_flash_attention: bool = False
    # GPipe microbatches when the mesh's ``pipe`` axis > 1 (0 = auto: the
    # largest of {4S, 2S, S} dividing the batch).  Bubble fraction is
    # (S-1)/(M+S-1), so prefer M >= 4S.
    pipe_microbatches: int = 0
    # Pipeline schedule at pipe>1: "gpipe" (autodiff through the forward
    # scan — O(M) activation stash) or "1f1b" (combined fwd/bwd scan with
    # a depth-(2S-1) input ring stash + remat backward — the deep-pipe
    # memory answer; parallel/pipeline.py).  Same math either way.
    pipe_schedule: str = "gpipe"
    # Ring attention kv-chunk size (0 = whole per-shard blocks): bounds the
    # per-ring-step score tile to (T/shards, ring_chunk_size) — set for
    # pod-scale per-shard sequence lengths (see parallel.ring_attention).
    ring_chunk_size: int = 0
    # Cross-entropy chunk length (0 = full (B, T, V) logits).  With a
    # 50k vocabulary the logits are the step's biggest tensor (batch 24:
    # 4.9 GiB f32); chunking computes logits+CE per T-chunk under a
    # rematerialized scan, so only (B, chunk, V) is ever live.
    ce_chunk: int = 0

    @classmethod
    def small(cls, **kw):
        return cls(d_model=768, n_layer=12, n_head=12, **kw)

    @classmethod
    def medium(cls, **kw):  # 355M — the reference's config
        # unroll=4 measured best on v5e in a round that predates the
        # current chip attachment: fewer scan iterations amortize the
        # stacked-grad DUS writes.
        kw.setdefault("scan_unroll", 4)
        return cls(d_model=1024, n_layer=24, n_head=16, **kw)

    @classmethod
    def tiny(cls, **kw):  # tests
        return cls(vocab_size=256, n_positions=128, d_model=64, n_layer=2,
                   n_head=4, dropout=0.0, **kw)

    @classmethod
    def mini(cls, **kw):  # CPU serve-bench scale
        # Big enough that a long prompt's prefill COMPUTE dominates the
        # per-launch dispatch overhead on CPU (tiny is the opposite —
        # every launch costs about the same regardless of tokens), so
        # scheduling effects like chunked prefill's head-of-line relief
        # are measurable without a TPU; small enough to compile and
        # serve a bench run in seconds.
        return cls(vocab_size=256, n_positions=512, d_model=256, n_layer=4,
                   n_head=8, dropout=0.0, **kw)


def _quantize_kv_int8(x):
    """Symmetric per-token int8: one f32 scale per (row, position), shared
    across heads — write-local, so appending a token never rescales data
    already in the block."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=(-2, -1)) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(xf / scale[..., None, None]), -127, 127)
    return q.astype(jnp.int8), scale


class Block(nn.Module):
    cfg: GPT2Config
    mesh: Optional[Mesh] = None
    deterministic: bool = True  # attribute (not call arg) so nn.scan can map
    decode: bool = False  # KV-cache incremental decode (serve path)
    paged: Optional[PagedKVConfig] = None  # block-table cache (serve path)

    @nn.compact
    def __call__(self, x, slot_ids=None, block_tables=None, live=None,
                 layer=None):
        cfg = self.cfg
        deterministic = self.deterministic
        d, h = cfg.d_model, cfg.n_head
        head_dim = d // h
        B, T, _unused = x.shape

        y = nn.LayerNorm(dtype=jnp.float32, name="ln_1")(x)
        qkv = nn.Dense(3 * d, dtype=cfg.dtype, name="c_attn")(y)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, h, head_dim)
        k = k.reshape(B, T, h, head_dim)
        v = v.reshape(B, T, h, head_dim)
        if self.decode and self.paged is not None:
            # Paged serve path: K/V in a fixed pool of blocks, each slot's
            # logical positions routed through its block-table row.
            ctx = self._paged_cached_attention(
                q, k, v, slot_ids, block_tables, live, layer,
            ).reshape(B, T, d)
        elif self.decode:
            # Serve path: exact attention over the preallocated KV cache.
            # Takes precedence over ring/flash — both are training-shape
            # kernels; decode works on (B, 1, ...) steps against the cache.
            ctx = self._cached_attention(q, k, v, slot_ids).reshape(B, T, d)
        elif self.mesh is not None and self.mesh.shape.get("context", 1) > 1:
            # Long-context path: sequence sharded over the context axis, KV
            # rotating over the ICI ring (parallel.ring_attention).  Exact
            # attention incl. attention-prob dropout (per-block dropout
            # composes exactly under the lse combine).
            drop = 0.0 if deterministic else cfg.dropout
            ctx = ring_attention(
                q, k, v, mesh=self.mesh, causal=True,
                chunk_size=cfg.ring_chunk_size or None,
                dropout_rate=drop,
                dropout_rng=self.make_rng("dropout") if drop > 0 else None,
            ).reshape(B, T, d)
        elif cfg.use_flash_attention:
            # Attention-prob dropout runs IN-KERNEL (TPU PRNG, identical
            # keep mask regenerated in backward) — the flash path keeps the
            # dense path's training recipe.  Under a mesh the kernel runs
            # per (batch, head) shard inside a shard_map (``mesh=``).
            drop = 0.0 if deterministic else cfg.dropout
            ctx = flash_attention(
                q, k, v, causal=True, dropout_rate=drop,
                dropout_rng=self.make_rng("dropout") if drop > 0 else None,
                mesh=self.mesh,
            ).reshape(B, T, d)
        else:
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(head_dim)
            mask = jnp.tril(jnp.ones((T, T), bool))
            scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            probs = probs.astype(cfg.dtype)
            probs = nn.Dropout(cfg.dropout, deterministic=deterministic)(probs)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, T, d)
        attn_out = nn.Dense(d, dtype=cfg.dtype, name="c_proj")(ctx)
        attn_out = nn.Dropout(cfg.dropout, deterministic=deterministic)(attn_out)
        x = x + attn_out

        y = nn.LayerNorm(dtype=jnp.float32, name="ln_2")(x)
        mlp = nn.Dense(4 * d, dtype=cfg.dtype, name="mlp_c_fc")(y)
        mlp = nn.gelu(mlp, approximate=True)
        mlp = nn.Dense(d, dtype=cfg.dtype, name="mlp_c_proj")(mlp)
        mlp = nn.Dropout(cfg.dropout, deterministic=deterministic)(mlp)
        return x + mlp, None

    def _cached_attention(self, q, k, v, slot_ids=None):
        """Exact attention over a preallocated (B, S, H, hd) KV cache.

        The cache geometry (S = max decode length) is fixed by the shape of
        the ``decode=True`` init call; afterwards any call length T works as
        long as ``cache_index + T <= S`` — one call with the whole prompt
        (prefill), then T=1 steps.  Keys at positions ``> cache_index +
        query_offset`` are masked, so right-padding the cache never leaks
        into the softmax.  Heads shard over the ``tensor`` axis exactly like
        the training path (the cache rides the same column-parallel qkv
        layout — see ``gpt2_cache_rules``).

        ``slot_ids=None`` is the fixed-batch path: ONE scalar
        ``cache_index``, the whole batch advances in lockstep.  With
        ``slot_ids`` (shape ``(B_call,)``, unique) the cache is a RESIDENT
        slot table for continuous batching: ``cache_index`` is a
        ``(num_slots,)`` vector, the call's rows are gathered from /
        scattered back to their slots, and each row's K/V lands at its OWN
        per-slot offset (``vmap``-ed ``dynamic_update_slice``), so requests
        at different decode depths share one cache and one program.
        """
        cfg = self.cfg
        B, T, h, head_dim = q.shape
        slot_mode = slot_ids is not None
        ck = self.variable(
            "cache", "cached_key",
            lambda: jnp.zeros((B, T, h, head_dim), cfg.dtype))
        cv = self.variable(
            "cache", "cached_value",
            lambda: jnp.zeros((B, T, h, head_dim), cfg.dtype))
        ci = self.variable(
            "cache", "cache_index",
            lambda: jnp.zeros((B,) if slot_mode else (), jnp.int32))
        if slot_mode:
            idx = ci.value[slot_ids]                      # (B,) per-slot
            rows_k = ck.value[slot_ids]                   # (B, S, h, hd)
            rows_v = cv.value[slot_ids]
            write = jax.vmap(
                lambda row, new, off: lax.dynamic_update_slice(
                    row, new, (off, 0, 0)))
            rows_k = write(rows_k, k.astype(ck.value.dtype), idx)
            rows_v = write(rows_v, v.astype(cv.value.dtype), idx)
            ck.value = ck.value.at[slot_ids].set(rows_k)
            cv.value = cv.value.at[slot_ids].set(rows_v)
            ci.value = ci.value.at[slot_ids].set(idx + T)
            S = rows_k.shape[1]
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q, rows_k) / np.sqrt(head_dim)
            q_pos = idx[:, None] + jnp.arange(T)[None, :]   # (B, T)
            mask = jnp.arange(S)[None, None, :] <= q_pos[:, :, None]
            scores = jnp.where(
                mask[:, None], scores, jnp.finfo(scores.dtype).min)
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            probs = probs.astype(cfg.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, rows_v)
        idx = ci.value
        k_all = lax.dynamic_update_slice(
            ck.value, k.astype(ck.value.dtype), (0, idx, 0, 0))
        v_all = lax.dynamic_update_slice(
            cv.value, v.astype(cv.value.dtype), (0, idx, 0, 0))
        ck.value, cv.value, ci.value = k_all, v_all, idx + T
        S = k_all.shape[1]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_all) / np.sqrt(head_dim)
        q_pos = idx + jnp.arange(T)
        mask = jnp.arange(S)[None, :] <= q_pos[:, None]  # (T, S) causal
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        probs = probs.astype(cfg.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v_all)

    def _paged_cached_attention(self, q, k, v, slot_ids, block_tables,
                                live=None, layer=None):
        """Exact attention over the block-table KV pool.

        K/V storage is a ``(num_blocks, block_size, H * hd)`` pool (heads
        merged into the lane-dense minor dimension); logical position
        ``p`` of slot ``s`` lives at physical block
        ``block_tables[s, p // block_size]``, offset ``p % block_size``.
        Each call scatters its new K/V into the owning blocks (one write
        per (row, token) — offsets are unique within a call because slot
        ids are), then attends over each row's first ``cache_index + T``
        positions.  Unallocated table entries point at trash block 0,
        whose (finite garbage) contents sit past each row's
        ``cache_index``.

        ``layer`` is the scanned stack's layer index: the cache variables
        are then the WHOLE ``(layers, ...)`` stacks, carried through the
        layer loop, and this layer scatters at ``[layer, block, offset]``
        and reads ``pool[layer]`` through the table — the pool is updated
        in place and never sliced or re-stacked per layer.  ``None`` (the
        unrolled ``h_i`` layout, and init) means the variables are this
        layer's own.

        One algorithm, two implementations of the attention that follows
        the scatter, chosen by what the call can observe
        (``ops.paged_attention.supported``), never by a flag:

        - **The kernel** (``ops.paged_attention.paged_decode_attention``)
          for a decode step (``T == 1``) over a pool stored in the compute
          type, on one TPU device (or under ``DTT_PALLAS_INTERPRET=1``): it
          walks the table, fetches only the blocks a row's length covers
          straight from the pool, and folds them into an online softmax
          with f32 scores.  ``live`` (``(B,)`` bool, the caller's mask of
          rows whose step counts) lets it skip dead and inactive rows
          altogether: they fetch nothing and attend to nothing.  Its sums
          run in another order than the gather path's, so a greedy token
          may differ from that path's where two logits tie within bf16
          rounding.
        - **The gather path**, for everything else (prefill and verify
          shapes, int8 and cast-on-write pools, sharded pools, the CPU) and
          as the reference the kernel is tested against: it gathers the
          slot's whole table row back into a contiguous ``(B, max_blocks *
          block_size, H, hd)`` view for the same masked softmax as the
          dense slot path; trash entries are causally masked.  When the
          storage dtype equals the compute dtype and ``max_blocks *
          block_size == max_total_len``, its post-gather math is
          shape-identical to the dense slot path — greedy streams match it
          token for token.  ``live`` changes nothing here.

        With ``kv_dtype="int8"`` the pool stores per-token symmetrically
        quantized values plus ``(num_blocks, block_size)`` f32 scale
        tables, dequantized in the gather; any other ``kv_dtype`` is a
        plain cast on write.

        Prefix caching rides on this unchanged: a suffix prefill arrives
        with ``cache_index`` preset to the block-aligned start, so the
        scatter only writes positions ``>= start`` (shared prefix blocks
        are never touched) while the gather still pulls the slot's WHOLE
        table row — the mapped cached blocks below ``start`` — and the
        ``k_pos <= q_pos`` causal mask admits them for every query; a
        decode step after it reaches the shared blocks through the table
        like any other.
        """
        cfg, pg = self.cfg, self.paged
        B, T, h, head_dim = q.shape
        bs, d = pg.block_size, h * head_dim
        store_dtype = pg.storage_dtype(cfg.dtype)
        kp = self.variable(
            "cache", "cached_key_pool",
            lambda: jnp.zeros((pg.num_blocks, bs, d), store_dtype))
        vp = self.variable(
            "cache", "cached_value_pool",
            lambda: jnp.zeros((pg.num_blocks, bs, d), store_dtype))
        if pg.quantized:
            ksc = self.variable(
                "cache", "key_scale",
                lambda: jnp.zeros((pg.num_blocks, bs), jnp.float32))
            vsc = self.variable(
                "cache", "value_scale",
                lambda: jnp.zeros((pg.num_blocks, bs), jnp.float32))
        ci = self.variable(
            "cache", "cache_index",
            lambda: jnp.zeros((B,), jnp.int32))

        def at(*index):
            return index if layer is None else (layer,) + index

        idx = ci.value[at(slot_ids)]                          # (B,)
        rows_bt = jnp.maximum(block_tables, 0)[slot_ids]      # (B, max_blk)
        pos = idx[:, None] + jnp.arange(T)[None, :]           # (B, T)
        pb = jnp.take_along_axis(rows_bt, pos // bs, axis=1)  # (B, T)
        off = pos % bs
        cells = at(pb.reshape(-1), off.reshape(-1))
        if pg.quantized:
            kq, k_scale = _quantize_kv_int8(k)
            vq, v_scale = _quantize_kv_int8(v)
            kp.value = kp.value.at[cells].set(kq.reshape(B * T, d))
            vp.value = vp.value.at[cells].set(vq.reshape(B * T, d))
            ksc.value = ksc.value.at[cells].set(k_scale.reshape(-1))
            vsc.value = vsc.value.at[cells].set(v_scale.reshape(-1))
        else:
            kp.value = kp.value.at[cells].set(
                k.astype(store_dtype).reshape(B * T, d))
            vp.value = vp.value.at[cells].set(
                v.astype(store_dtype).reshape(B * T, d))
        ci.value = ci.value.at[at(slot_ids)].set(idx + T)

        if paged_attention.supported(
                query_len=T, block_size=bs, width=d, pool_dtype=store_dtype,
                compute_dtype=cfg.dtype, mesh=self.mesh,
                data_shards=pg.data_shards):
            paged_attention.note_path(paged_attention.KERNEL)
            lengths = idx + T
            if live is not None:
                lengths = jnp.where(live, lengths, 0)
            return paged_attention.paged_decode_attention(
                q, kp.value, vp.value, rows_bt, lengths, layer=layer)
        paged_attention.note_path(paged_attention.GATHER)
        rows = at(rows_bt)
        gk = kp.value[rows]                   # (B, max_blk, bs, H * hd)
        gv = vp.value[rows]
        if pg.quantized:
            gk = (gk.astype(jnp.float32)
                  * ksc.value[rows][..., None]).astype(cfg.dtype)
            gv = (gv.astype(jnp.float32)
                  * vsc.value[rows][..., None]).astype(cfg.dtype)
        else:
            gk = gk.astype(cfg.dtype)
            gv = gv.astype(cfg.dtype)
        S = rows_bt.shape[1] * bs
        gk = gk.reshape(B, S, h, head_dim)
        gv = gv.reshape(B, S, h, head_dim)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, gk) / np.sqrt(head_dim)
        q_pos = idx[:, None] + jnp.arange(T)[None, :]         # (B, T)
        mask = jnp.arange(S)[None, None, :] <= q_pos[:, :, None]
        scores = jnp.where(
            mask[:, None], scores, jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        probs = probs.astype(cfg.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, gv)


class GPT2(nn.Module):
    cfg: GPT2Config
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, tokens, *, deterministic: bool = True,
                 return_hidden: bool = False, decode: bool = False,
                 slot_ids=None, paged: Optional[PagedKVConfig] = None,
                 block_tables=None, live=None):
        cfg = self.cfg
        B, T = tokens.shape
        if slot_ids is not None and not decode:
            raise ValueError("slot_ids only applies to decode=True calls")
        if paged is not None:
            if slot_ids is None:
                raise ValueError(
                    "paged KV cache requires slot_ids (the block table is "
                    "indexed per slot; only the continuous-batching slot "
                    "path is paged)")
            if block_tables is None:
                raise ValueError(
                    "paged=... requires block_tables, the (num_slots, "
                    "max_blocks_per_slot) int32 logical->physical block map")
        elif block_tables is not None:
            raise ValueError("block_tables only applies with paged=...")
        if live is not None and paged is None:
            raise ValueError(
                "live (the mask of rows whose step counts) only applies "
                "with paged=...")
        wte = self.param(
            "wte",
            nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.d_model),
            jnp.float32,
        )
        wpe = self.param(
            "wpe",
            nn.initializers.normal(0.01),
            (cfg.n_positions, cfg.d_model),
            jnp.float32,
        )
        if decode:
            # KV-cache decode (serve path): positions continue from where
            # the cache left off.  The init call (full max-length input)
            # fixes the cache geometry; apply calls advance ``position``.
            # With ``slot_ids`` (continuous batching) ``position`` is a
            # per-slot (num_slots,) vector — each row of the call gets its
            # own wpe offset and only its slots' entries advance.
            pos = self.variable(
                "cache", "position",
                lambda: jnp.zeros((B,) if slot_ids is not None else (),
                                  jnp.int32))
            if slot_ids is not None:
                offset = pos.value[slot_ids]              # (B,)
                positions = offset[:, None] + jnp.arange(T)[None, :]
                x = (wte[tokens].astype(cfg.dtype)
                     + wpe[positions].astype(cfg.dtype))
                pos.value = pos.value.at[slot_ids].set(offset + T)
            else:
                offset = pos.value
                x = wte[tokens].astype(cfg.dtype) + lax.dynamic_slice(
                    wpe, (offset, 0), (T, cfg.d_model)).astype(cfg.dtype)
                pos.value = offset + T
        else:
            x = wte[tokens].astype(cfg.dtype) + wpe[:T].astype(cfg.dtype)
        x = nn.Dropout(cfg.dropout, deterministic=deterministic)(x)
        pipe = self.mesh.shape.get("pipe", 1) if self.mesh is not None else 1
        if decode and pipe > 1:
            raise ValueError(
                "decode=True with pipe>1 is unsupported: the serve engine "
                "runs the scanned block stack directly (TP/DP shardings "
                "apply; re-mesh without a pipe axis to serve)"
            )
        if cfg.scan_layers and pipe > 1 and not self.is_initializing():
            # GPipe path: same "blocks" parameter layout as the scanned
            # stack (checkpoints and sharding rules are layout-stable in
            # --pipe), applied through the pipeline schedule instead of a
            # sequential scan.  Init still goes through nn.scan below.
            if not deterministic and cfg.dropout > 0:
                raise ValueError(
                    "pipe>1 runs blocks deterministically (GPipe stage fn "
                    "carries no per-layer rng); set dropout=0 — "
                    "make_workload does this automatically"
                )
            x = self._pipelined_blocks(x)
        elif cfg.scan_layers and paged is not None and (
                not self.is_initializing()):
            x = self._paged_blocks(x, slot_ids, block_tables, live, paged)
        elif cfg.scan_layers:
            # No remat in decode: there is no backward pass, and remat's
            # lifted scope rejects the mutable cache writes.  (The paged
            # serve path has its own loop above; its init, which only
            # fixes the shapes, stacks per-layer variables here.)
            body = Block if decode or not cfg.remat else nn.remat(
                Block, prevent_cse=False, policy=REMAT_POLICY)
            Scanned = nn.scan(
                body,
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=nn.broadcast,  # slot_ids/tables: every layer's
                length=cfg.n_layer,
                unroll=cfg.scan_unroll,
            )
            x, _ = Scanned(
                cfg, mesh=self.mesh, deterministic=deterministic,
                decode=decode, paged=paged, name="blocks",
            )(x, slot_ids, block_tables)
        else:
            for i in range(cfg.n_layer):
                x, _ = Block(
                    cfg, mesh=self.mesh, deterministic=deterministic,
                    decode=decode, paged=paged, name=f"h_{i}",
                )(x, slot_ids, block_tables, live)
        x = nn.LayerNorm(dtype=jnp.float32, name="ln_f")(x)
        if return_hidden:
            # Chunked-CE path: the loss computes logits per T-chunk itself
            # (the tied wte comes from the params tree), so the (B, T, V)
            # buffer never materializes.
            return x
        # Weight-tied head: bf16 operands on the MXU (f32 runs at half the
        # MXU rate on v5e), f32 accumulation/output for a stable softmax.
        logits = jnp.einsum(
            "btd,vd->btv",
            x.astype(cfg.dtype),
            wte.astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )
        return logits

    def _paged_blocks(self, x, slot_ids, block_tables, live, paged):
        """The scanned stack over the paged cache (the served path): a loop
        of its own, for two things ``nn.scan`` over axis 0 would copy.

        The cache is CARRIED through the layer loop: each layer updates the
        stacked (L, ...) pools in place at its own index.  Scanned over
        axis 0 they would be sliced per layer and re-stacked, a copy of
        every pool each token step.  And a layer's leaves are taken from
        the (L, ...) parameter stack by that index, where a product reads
        them: scanned over axis 0 with the stack's unroll, four layers'
        kernels are sliced out together, a copy of all the weights each
        pass (into fast memory while four layers fit there, through HBM
        where they do not) before any product reads them."""
        cfg = self.cfg
        block = Block(cfg, mesh=self.mesh, deterministic=True, decode=True,
                      paged=paged)
        stack = self.scope.get_variable("params", "blocks")

        def body(carry, layer):
            h, cache = carry
            leaves = jax.tree.map(
                lambda w: lax.dynamic_index_in_dim(w, layer, keepdims=False),
                stack)
            (h, _), mutated = block.apply(
                {"params": leaves, "cache": cache}, h, slot_ids,
                block_tables, live, layer, mutable=["cache"])
            return (h, mutated["cache"]), None

        (x, cache), _ = lax.scan(
            body, (x, self.scope.get_variable("cache", "blocks")),
            jnp.arange(cfg.n_layer, dtype=jnp.int32),
            unroll=cfg.scan_unroll)
        self.scope.put_variable("cache", "blocks", cache)
        return x

    def _pipelined_blocks(self, x):
        """Apply the scanned block stack through the GPipe schedule.

        The (L, ...) "blocks" parameters are re-viewed as (S, L/S, ...) —
        S contiguous stages of L/S layers — and fed to
        ``parallel.pipeline.pipeline_apply`` (shard_map manual over ``pipe``
        only, so TP/DP inside each stage stay GSPMD-driven).  Embeddings,
        final LN, and the LM head run outside the pipeline, replicated over
        the pipe axis.  Stage construction is shared with the 1F1B path
        (``_pipe_stage_fn``/``_pipe_staging``) so the two schedules cannot
        drift apart structurally.
        """
        from distributed_tensorflow_tpu.parallel.pipeline import (
            pipeline_apply,
        )

        params = self.scope.get_variable("params", "blocks")
        staged, xm, _ = _pipe_staging(self.cfg, self.mesh, params, x)
        y = pipeline_apply(_pipe_stage_fn(self.cfg, self.mesh), staged, xm,
                           mesh=self.mesh, axis="pipe")
        return jnp.reshape(y, x.shape)


def _pipe_stage_fn(cfg, mesh):
    """One pipeline stage = a scan over its L/S layers (remat per layer),
    SHARED by the GPipe (``_pipelined_blocks``) and 1F1B
    (``_pipe_1f1b_loss``) paths — one definition, zero schedule drift.
    The block keeps the mesh: the stage runs manual over ``pipe`` only, and
    the flash kernel nests its own shard_map over the remaining axes."""
    block = Block(cfg, mesh=mesh, deterministic=True)

    def stage_fn(stage_params, h):
        def body(h, layer_params):
            h, _ = block.apply({"params": layer_params}, h)
            return h, None

        if cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False,
                                  policy=REMAT_POLICY)
        h, _ = lax.scan(body, h, stage_params)
        return h

    return stage_fn


def _pipe_staging(cfg, mesh, blocks_params, x):
    """(staged blocks params, microbatched x, M) for the pipeline paths.

    Re-views (L, ...) block params as (S, L/S, ...) contiguous stages and
    the (B, ...) batch as (M, B/M, ...) microbatches, with the microbatch
    dim kept data-sharded.  Shared by both schedules (see _pipe_stage_fn).
    """
    S = mesh.shape["pipe"]
    L = cfg.n_layer
    if L % S != 0:
        raise ValueError(f"n_layer={L} not divisible by pipe={S}")
    staged = jax.tree.map(
        lambda p: jnp.reshape(p, (S, L // S) + p.shape[1:]), blocks_params
    )
    B = x.shape[0]
    M = cfg.pipe_microbatches or _auto_microbatches(B, S)
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    xm = jnp.reshape(x, (M, B // M) + x.shape[1:])
    xm = jax.lax.with_sharding_constraint(
        xm, jax.sharding.NamedSharding(mesh, P(None, ("data", "fsdp")))
    )
    return staged, xm, M


def _auto_microbatches(batch: int, n_stages: int) -> int:
    """Largest of {4S, 2S, S} dividing the batch (bubble <= (S-1)/(5S-1))."""
    for m in (4 * n_stages, 2 * n_stages, n_stages):
        if batch >= m and batch % m == 0:
            return m
    raise ValueError(
        f"global batch {batch} is not divisible by any of "
        f"{{4,2,1}}x pipe={n_stages} microbatch counts"
    )


def _chunked_ce(hidden, wte, tokens, chunk, dtype):
    """Mean next-token CE without materializing (B, T, V) logits.

    Scans T in ``chunk``-length pieces; each step computes that chunk's
    logits (bf16 MXU operands, f32 accumulation) and its CE, then drops
    them — ``jax.checkpoint`` makes backward recompute the chunk logits
    instead of saving them, so peak memory is one (B, chunk, V) tile.
    """
    B, T, d = hidden.shape
    if T % chunk:
        raise ValueError(f"seq_len {T} not divisible by ce_chunk {chunk}")
    n = T // chunk
    # Shifted targets with the final position masked (no next token).
    tgt = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1)
    valid = (jnp.arange(T) < T - 1).astype(jnp.float32)
    hs = jnp.moveaxis(hidden.reshape(B, n, chunk, d), 1, 0)
    ts = jnp.moveaxis(tgt.reshape(B, n, chunk), 1, 0)
    ws = valid.reshape(n, chunk)

    def body(total, xs):
        h, t, w = xs
        logits = jnp.einsum(
            "bcd,vd->bcv", h.astype(dtype), wte.astype(dtype),
            preferred_element_type=jnp.float32,
        )
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, t)
        return total + jnp.sum(ce * w[None, :]), None

    total, _ = lax.scan(
        jax.checkpoint(body, prevent_cse=False), jnp.float32(0.0),
        (hs, ts, ws),
    )
    return total / (B * (T - 1))


def _tied_head_ce(hidden, wte, tokens, dtype):
    """Weight-tied LM head + shifted next-token mean CE — THE training
    recipe in one place, shared by the dense path (``_loss_fn``) and the
    1F1B tail (``_pipe_1f1b_loss``); ``_chunked_ce`` mirrors it per
    T-chunk.  bf16 operands on the MXU (f32 runs at half the MXU rate on
    v5e), f32 accumulation/output for a stable softmax."""
    logits = jnp.einsum(
        "btd,vd->btv",
        hidden.astype(dtype),
        wte.astype(dtype),
        preferred_element_type=jnp.float32,
    )
    return jnp.mean(
        optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]
        )
    )


def _pipe_1f1b_loss(module: "GPT2", params, batch: Dict[str, jax.Array],
                    rng):
    """Training loss for ``--pipe`` under the 1F1B schedule.

    The GPipe path differentiates through ``pipeline_apply`` inside
    ``module.apply`` (autodiff stashes O(M) tick activations); this path
    drives ``parallel.pipeline.pipeline_value_and_grad(schedule="1f1b")``
    — forward AND backward are ONE combined scan with a depth-(2S-1)
    input ring stash — and hands the precomputed gradients to the
    standard train step
    through a ``custom_vjp`` whose backward merely scales them.
    Composition per ``PipelineVJP``'s docstring: token+position embedding
    under ``jax.vjp`` outside the schedule, the scanned block stack as
    stages, final LN + tied LM head + CE as the trainable tail on the last
    stage.  The tied ``wte`` gradient is the SUM of the embedding-path
    (via ``r.dx``) and head-path (``r.tail_grads``) cotangents.
    """
    from distributed_tensorflow_tpu.parallel.pipeline import (
        pipeline_value_and_grad,
    )

    cfg = module.cfg
    mesh = module.mesh
    tokens = batch["tokens"]
    B, T = tokens.shape
    d = cfg.d_model
    stage_fn = _pipe_stage_fn(cfg, mesh)
    ln_f = nn.LayerNorm(dtype=jnp.float32)

    def tail_fn(tp, y_mb, t_mb):
        h = ln_f.apply({"params": tp["ln_f"]}, y_mb)
        return _tied_head_ce(h, tp["wte"], t_mb, cfg.dtype)

    def _compute(p):
        def embed(wte, wpe):
            return wte[tokens].astype(cfg.dtype) + wpe[:T].astype(cfg.dtype)

        x, emb_vjp = jax.vjp(embed, p["wte"], p["wpe"])
        staged, xm, M = _pipe_staging(cfg, mesh, p["blocks"], x)
        tm = jnp.reshape(tokens, (M, B // M, T))
        r = pipeline_value_and_grad(
            stage_fn, None, staged, xm, tm, mesh=mesh, axis="pipe",
            schedule="1f1b", tail_fn=tail_fn,
            tail_params={"ln_f": p["ln_f"], "wte": p["wte"]},
        )
        d_wte_emb, d_wpe = emb_vjp(
            jnp.reshape(r.dx, (B, T, d)).astype(x.dtype)
        )
        grads = {
            "blocks": jax.tree.map(
                lambda g: jnp.reshape(g, (cfg.n_layer,) + g.shape[2:]),
                r.grads
            ),
            "ln_f": r.tail_grads["ln_f"],
            "wte": d_wte_emb + r.tail_grads["wte"],
            "wpe": d_wpe,
        }
        return r.loss, grads

    @jax.custom_vjp
    def pipe_loss(p):
        return _compute(p)[0]

    def _fwd(p):
        return _compute(p)

    def _bwd(grads, ct):
        return (jax.tree.map(lambda g: (g * ct).astype(g.dtype), grads),)

    pipe_loss.defvjp(_fwd, _bwd)
    loss = pipe_loss(params)
    return loss, {"perplexity": jnp.exp(jnp.minimum(loss, 20.0))}


def _loss_fn(module: nn.Module, deterministic: bool, params,
             batch: Dict[str, jax.Array], rng):
    tokens = batch["tokens"]
    cfg = module.cfg
    rngs = None if deterministic else {"dropout": rng}
    if cfg.ce_chunk:
        hidden = module.apply(
            {"params": params}, tokens, deterministic=deterministic,
            rngs=rngs, return_hidden=True,
        )
        loss = _chunked_ce(hidden, params["wte"], tokens, cfg.ce_chunk,
                           cfg.dtype)
        return loss, {"perplexity": jnp.exp(jnp.minimum(loss, 20.0))}
    hidden = module.apply(
        {"params": params}, tokens, deterministic=deterministic, rngs=rngs,
        return_hidden=True,
    )
    loss = _tied_head_ce(hidden, params["wte"], tokens, cfg.dtype)
    return loss, {"perplexity": jnp.exp(jnp.minimum(loss, 20.0))}


def gpt2_rules() -> ShardingRules:
    """TP/fsdp rules for this module's parameter names.

    Scanned layout ("blocks/...") parameters carry a leading layer dim —
    their specs lead with None so the TP/fsdp split lands on the same
    logical dims as the per-layer ("h_i/...") layout.
    """
    return transformer_rules().extended(
        [
            # scanned-stack layout: leading layer dim rides the pipe axis
            # (a no-op at pipe=1; stage-contiguous placement at pipe>1).
            (r"blocks/.*c_attn/kernel", P("pipe", "fsdp", "tensor")),
            (r"blocks/.*c_proj/kernel", P("pipe", "tensor", "fsdp")),
            (r"blocks/.*mlp_c_fc/kernel", P("pipe", "fsdp", "tensor")),
            (r"blocks/.*(bias|scale)", P("pipe")),
            # shared / per-layer layout
            (r"wte$", P("tensor", "fsdp")),
            (r"wpe$", P()),
            (r"mlp_c_fc/kernel", P("fsdp", "tensor")),
            (r"mlp_c_proj/kernel", P("tensor", "fsdp")),
        ]
    )


def gpt2_cache_rules(per_shard_pools: bool = False) -> ShardingRules:
    """Sharding for the decode KV cache ("cache" collection).

    Cached k/v are (B, S, H, head_dim) — (L, B, S, H, head_dim) under the
    scanned "blocks" layout — with the batch over the data axes and heads
    over ``tensor``, matching the column-parallel qkv projection the cache
    is written from (``transformer_rules``), so decode runs TP without any
    resharding at the cache boundary.  Scalar indices stay replicated.

    Paged pools are ``(num_blocks, block_size, H * head_dim)`` —
    ``(L, num_blocks, block_size, H * head_dim)`` under "blocks" — with the
    merged minor dimension over ``tensor``: heads are contiguous in it, so
    a chip holds the columns of exactly the heads it computes.

    ``per_shard_pools=True`` (``PagedKVConfig.data_shards > 1``) shards the
    paged pools' block dimension over the data axes as well: the allocator
    partitions block ids contiguously per data shard and pins every slot's
    table to its own shard, so each data shard holds ``num_blocks / data``
    physical blocks instead of a full replica — per-device KV HBM drops by
    the data-axis width.  Scale tables shard the same way (they are
    per-block rows).
    """
    if per_shard_pools:
        pool_rules = [
            (r"blocks/cached_(key|value)_pool",
             P(None, ("data", "fsdp"), None, "tensor")),
            (r"cached_(key|value)_pool",
             P(("data", "fsdp"), None, "tensor")),
            (r"blocks/(key|value)_scale", P(None, ("data", "fsdp"))),
            (r"(key|value)_scale", P(("data", "fsdp"))),
        ]
    else:
        pool_rules = [
            # Paged pools (L, num_blocks, block_size, H * hd): in the
            # replicated layout the block dim is NOT a batch dim — any
            # slot's tokens can live in any block — so only heads shard
            # (over ``tensor``: heads are contiguous in the merged minor
            # dimension, so each chip holds the columns of the heads the
            # qkv projection writes there); scale tables replicate.
            (r"blocks/cached_(key|value)_pool",
             P(None, None, None, "tensor")),
            (r"cached_(key|value)_pool", P(None, None, "tensor")),
            (r"(key|value)_scale", P()),
        ]
    return ShardingRules(
        pool_rules
        + [
            (r"blocks/cached_(key|value)",
             P(None, ("data", "fsdp"), None, "tensor")),
            (r"cached_(key|value)", P(("data", "fsdp"), None, "tensor")),
            (r"(cache_index|position)", P()),
        ]
    )


def _guard_dense_attention_memory(cfg, *, seq, batch_size, grad_accum_steps,
                                  mesh) -> None:
    """Refuse configs whose DENSE attention would OOM the chip.

    The non-flash path materializes (B, H, T, T) score/prob buffers (f32
    softmax + bf16 probs, forward AND recomputed in backward under remat).
    GPT-2 medium at seq 1024, per-chip microbatch 16 ran out of memory on a
    16 GB v5e — an opaque failure deep inside XLA allocation.  Guard
    here with the actionable fix, instead of an opaque RESOURCE_EXHAUSTED:
    turn on --flash_attention (streams the tiles through VMEM) or raise
    --grad_accum_steps (shrinks the microbatch).
    """
    if cfg.use_flash_attention:
        return
    if mesh is not None:
        dp = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
        ctx = mesh.shape.get("context", 1)
        if ctx > 1:
            return  # ring attention path; no (T, T) buffer
    else:
        dp = 1
    if os.environ.get("DTT_SKIP_DENSE_ATTN_GUARD", "") == "1":
        return
    micro = max(1, batch_size // (dp * max(1, grad_accum_steps)))
    # Attention heads shard over the tensor axis (column-parallel qkv), so
    # the per-chip score buffer carries H / tensor heads (ADVICE r3: a
    # valid TP config must not be falsely rejected).
    heads = cfg.n_head
    if mesh is not None:
        heads = max(1, heads // mesh.shape.get("tensor", 1))
    # ~6 live (micro, H, T, T) buffers around the softmax in the remat
    # backward (f32 scores + probs forward-recomputed, their cotangents,
    # bf16 probs both ways); calibrated to the measured boundary: medium/
    # seq-1024 OOMs at microbatch 16 (6.4 GiB by this model) and fits at
    # microbatch 4 (1.6 GiB) on a 16 GiB v5e.
    approx_bytes = 6 * micro * heads * seq * seq * 4
    # Budget = 1/4 of device memory (the rest is params/acts/grads), so
    # bigger-HBM chips (v4/v5p) get a proportionally higher ceiling.
    budget = _device_memory_bytes() // 4
    if approx_bytes > budget:
        raise ValueError(
            f"dense attention at microbatch {micro} x {cfg.n_head} heads x "
            f"seq {seq} needs ~{approx_bytes / 1024**3:.0f} GiB of (T, T) "
            "score buffers — this OOMs the chip. Enable --flash_attention "
            "(streams score tiles through VMEM, no (T, T) buffer) or raise "
            "--grad_accum_steps to shrink the per-chip microbatch."
        )


_ASSUMED_HBM_BYTES = 16 * 1024**3  # one v5e chip


def _device_memory_bytes() -> int:
    """This process's first device's memory limit (only addressable devices
    report stats).  A TPU that does not report one is an error; other
    backends (the CPU tests) get the v5e figure, logged."""
    dev = jax.local_devices()[0]
    limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    if limit:
        return limit
    if dev.platform == "tpu":
        raise RuntimeError(
            f"{dev} reports no memory_stats()['bytes_limit']; the dense-"
            "attention memory guard cannot size its budget (set "
            "DTT_SKIP_DENSE_ATTN_GUARD=1 to bypass it)")
    logger.info(
        "dense-attention guard: %s reports no memory limit; assuming %d GiB "
        "(one v5e chip)", dev.platform, _ASSUMED_HBM_BYTES // 1024**3)
    return _ASSUMED_HBM_BYTES


def make_workload(
    *,
    preset: str = "medium",
    batch_size: int = 32,
    seq_len: Optional[int] = None,
    grad_accum_steps: int = 4,
    config: Optional[GPT2Config] = None,
    mesh: Optional[Mesh] = None,
    use_flash_attention: Optional[bool] = None,
    ring_chunk_size: Optional[int] = None,
    ce_chunk: Optional[int] = None,
    pipe_schedule: Optional[str] = None,
    **_unused,
) -> Workload:
    cfg = config or getattr(GPT2Config, preset)()
    if use_flash_attention is not None:
        cfg = dataclasses.replace(cfg, use_flash_attention=use_flash_attention)
    if ring_chunk_size is not None:
        cfg = dataclasses.replace(cfg, ring_chunk_size=ring_chunk_size)
    if ce_chunk is not None:
        cfg = dataclasses.replace(cfg, ce_chunk=ce_chunk)
    if pipe_schedule is not None:
        cfg = dataclasses.replace(cfg, pipe_schedule=pipe_schedule)
    if cfg.pipe_schedule not in ("gpipe", "1f1b"):
        raise ValueError(
            f"pipe_schedule must be gpipe|1f1b, got {cfg.pipe_schedule!r}")
    if cfg.pipe_schedule == "1f1b" and not (
            mesh is not None and mesh.shape.get("pipe", 1) > 1):
        raise ValueError(
            "pipe_schedule='1f1b' requires a mesh with pipe>1; without one "
            "it would silently train the non-pipelined path instead of the "
            "schedule you asked for")
    if mesh is not None and mesh.shape.get("pipe", 1) > 1:
        if not cfg.scan_layers:
            raise ValueError(
                "pipe>1 requires scan_layers=True (the GPipe path stages "
                "the scanned block stack); the per-layer loop would "
                "silently replicate over the pipe axis"
            )
        if mesh.shape.get("context", 1) > 1:
            raise ValueError(
                "pipe>1 with context>1 is unsupported: pipeline stages run "
                "blocks locally (dense/flash attention), so the context "
                "axis would be inert; pick one"
            )
        if cfg.dropout > 0:
            logger.warning(
                "pipe>1: disabling dropout (GPipe stage fn is deterministic)"
            )
            cfg = dataclasses.replace(cfg, dropout=0.0)
    pipe_1f1b = (mesh is not None and mesh.shape.get("pipe", 1) > 1
                 and cfg.pipe_schedule == "1f1b")
    if pipe_1f1b and cfg.ce_chunk:
        raise ValueError(
            "ce_chunk with pipe_schedule='1f1b' is unsupported: the 1F1B "
            "tail computes each microbatch's logits in full (microbatches "
            "already bound the live logits to (B/M, T, V))")
    seq = seq_len or min(cfg.n_positions, 1024)
    _guard_dense_attention_memory(
        cfg, seq=seq, batch_size=batch_size,
        grad_accum_steps=grad_accum_steps, mesh=mesh,
    )
    module = GPT2(cfg, mesh=mesh)
    # Init batch must divide over the batch-sharding axes (ring attention is
    # a shard_map program with static per-shard shapes), like wide_deep.
    b0 = 2
    if mesh is not None:
        b0 = max(2, mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1))
    return Workload(
        name="gpt2",
        module=module,
        loss_fn=(functools.partial(_pipe_1f1b_loss, module) if pipe_1f1b
                 else functools.partial(_loss_fn, module, False)),
        eval_loss_fn=functools.partial(_loss_fn, module, True),
        init_batch={"tokens": np.zeros((b0, seq), np.int32)},
        data_fn=lambda per_host_bs: synthetic_lm(
            batch_size=per_host_bs, seq_len=seq, vocab_size=cfg.vocab_size,
        ),
        eval_data_fn=lambda per_host_bs: synthetic_lm(
            batch_size=per_host_bs, seq_len=seq, vocab_size=cfg.vocab_size,
            holdout=True,
        ),
        rules=gpt2_rules(),
        batch_size=batch_size,
        grad_accum_steps=grad_accum_steps,
        clip_grad_norm=1.0,
        learning_rate=3e-4,
        warmup_steps=200,
        example_key="tokens",
        init_key="tokens",
        cache_rules=gpt2_cache_rules,
        cache_geometry=functools.partial(_cache_geometry, cfg),
        served_dtypes=functools.partial(_served_dtypes, cfg),
    )


def _cache_geometry(cfg: GPT2Config, paged: PagedKVConfig) -> Dict[str, Any]:
    """What a token costs in the paged K and V pools."""
    itemsize = jnp.dtype(paged.storage_dtype(cfg.dtype)).itemsize
    return {
        "kind": "key_value",
        "pools_per_layer": 2,
        "values_per_token_layer": 2 * cfg.d_model,
        "pool_width": cfg.d_model,
        "padding_values": 0,
        "bytes_per_token_layer": 2 * cfg.d_model * itemsize,
        "bytes_per_token": cfg.n_layer * 2 * cfg.d_model * itemsize,
        "pool_bytes": (cfg.n_layer * 2 * paged.num_blocks * paged.block_size
                       * cfg.d_model * itemsize),
    }


# The leaves every served program reads only through a cast to
# ``cfg.dtype``: a ``Dense`` promotes its kernel and bias to its ``dtype``
# at each use, and the embedding, the positions and the tied head read
# ``wte`` and ``wpe`` through ``.astype(cfg.dtype)``.  Not the layer norms'
# scale and bias (``ln_1``, ``ln_2``, ``ln_f``), which are read in float32.
_CAST_AT_USE = ("wte", "wpe")
_DENSE = ("c_attn", "c_proj", "mlp_c_fc", "mlp_c_proj")


def _served_dtypes(cfg: GPT2Config, params) -> Any:
    """The type a server holds each parameter in
    (``Workload.served_dtypes``): rounding such a leaf once, where the
    weights enter the engine, gives the bits that rounding it in every
    launch gave."""

    def one(path, leaf):
        *_, module, name = [None] + _path_str(path).split("/")
        cast = name in _CAST_AT_USE or (
            module in _DENSE and name in ("kernel", "bias"))
        return jnp.dtype(cfg.dtype) if cast else leaf.dtype

    return jax.tree_util.tree_map_with_path(one, params)
