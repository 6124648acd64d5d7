"""One call's view of the paged cache, for the decoder families that serve
through the continuous scheduler's block tables and nothing else.

The scheduler's side of this seam is ``Workload.cache_geometry`` /
``.cache_rules`` / ``.serve_refusals`` / ``.served_dtypes`` and
``ops/paged_attention.supported``: ``serve/continuous.py`` names no family
and no pool leaf.  This is the models' side.  A family builds one
``PagedCall`` at the head of its ``__call__`` and asks it what four modules
used to know each for itself: which arguments go together, how a position
becomes a pool cell, what a row that is not live reads, by how much the
index advances and in which rows, and how a grouped-query layer's K/V is
written and read back.  The window ring's cell arithmetic and a per-slot
recurrent state stay with the family that has them and take ``positions``,
``table`` and ``lengths`` from here.  A long context walked through the
table a piece at a time (``ContextWalk``: the cached index scores of a
learned selection, the expanded latent attention of a prefill chunk under a
mask) is here since a second family reads a selection; the walk takes a
kind's sizes (``decoder_parts.MlaSizes``) and names no family.

The module also holds the one table of serving refusals
(``serve_refusals``) and the one construction of a family's ``Workload``
(``decoder_workload``).  It keeps no state of its own and takes no option:
nothing here branches on a family.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax.numpy as jnp
import numpy as np
from jax import lax

from distributed_tensorflow_tpu.data.pipeline import synthetic_lm
from distributed_tensorflow_tpu.models import Workload
from distributed_tensorflow_tpu.models.decoder_parts import (
    COUNT_EXTRA, cache_rules, dot, gqa_attend, index_scores, lm_loss,
    mla_expanded_scores, rms_norm)
from distributed_tensorflow_tpu.ops import paged_attention
from distributed_tensorflow_tpu.parallel.sharding import ShardingRules


class PagedCall:
    """``B`` rows of ``T`` positions against the paged cache (``decode``), or
    against nothing (a full forward, a loss): then ``positions`` is the plain
    ``0 .. T - 1``, every cache attribute is None, ``pool`` gives None and
    ``advance`` and ``close`` do nothing.

    In a cached call: ``start`` ``(B,)`` is each row's position before the
    call, ``positions`` ``(B, T)`` those it adds, ``table`` ``(B, blocks)``
    the rows' block-table rows with trash entries clipped to block 0 (and
    ``ring`` the window ring's entries, where ``paged`` has one), ``cells``
    the (block, offset) pair of every position, flat, as ``write`` takes it.
    ``lengths`` ``(B,)`` is each row's length after the call, 0 where
    ``live`` is false (such a row reads nothing), and ``token_live`` repeats
    ``live`` a position.  Both are computed where they are read, so a family
    reads each once, before its layer loop (a value traced inside a scanned
    body cannot leave it).

    ``pools`` is the family's noun for what it caches, for the error text;
    ``refusals`` its ``SERVE_REFUSALS``; ``experts`` ``(expert layers,
    held)`` where it counts the router's choices (``moe_counts``).  The
    leaf names ``cache_index`` and ``moe_counts`` are the engine's to find.
    """

    def __init__(self, module, B, T, *, decode, slot_ids, paged,
                 block_tables, live, pools, refusals, experts=None):
        if decode and (paged is None or slot_ids is None
                       or block_tables is None):
            raise ValueError(
                f"{pools} (paged only): decode=True needs slot_ids, "
                "paged=PagedKVConfig(...) and block_tables (the continuous "
                "scheduler's cache_mode='paged'); there is no dense-row or "
                "fixed-batch cache of this family")
        if not decode and (paged is not None or slot_ids is not None
                           or block_tables is not None or live is not None):
            raise ValueError(
                "slot_ids, paged, block_tables and live only apply to "
                "decode=True calls")
        if paged is not None:
            if paged.quantized or paged.kv_dtype is not None:
                raise ValueError(
                    f"kv_dtype {paged.kv_dtype!r}: {refusals['kv_dtype']}")
            if paged.data_shards != 1:
                raise ValueError(refusals["per_shard_kv"])
        self.module, self.B, self.T = module, B, T
        self.cached, self.paged = decode, paged
        self.slot_ids, self.live = slot_ids, live
        self._leaves = []
        if not decode:
            self.positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
            self.start = self.table = self.ring = self.cells = None
            return
        self._index = module.variable(
            "cache", "cache_index", lambda: jnp.zeros((B,), jnp.int32))
        if experts is not None:
            layers, held = experts
            self._counts = module.variable(
                "cache", "moe_counts", lambda: jnp.zeros(
                    (layers, held + COUNT_EXTRA), jnp.int32))
        bs = paged.block_size
        self.start = self._index.value[slot_ids]                      # (B,)
        self.positions = self.start[:, None] + jnp.arange(T)[None, :]  # (B, T)
        self.table, self.ring = paged.split_tables(
            jnp.maximum(block_tables, 0)[slot_ids])
        # Position p lies in the row's p // bs-th block, at p % bs.
        self.cells = (jnp.take_along_axis(
            self.table, self.positions // bs, axis=1).reshape(-1),
            (self.positions % bs).reshape(-1))

    @property
    def lengths(self):
        if not self.cached:
            return None
        after = self.start + self.T
        return after if self.live is None else jnp.where(self.live, after, 0)

    @property
    def token_live(self):
        return None if self.live is None else jnp.repeat(self.live, self.T)

    def advance(self):
        """The index moves past this call's positions, in the rows of
        ``slot_ids`` and nowhere else: once a call, after what the family
        works out from ``positions`` and before its layers."""
        if self.cached:
            self._index.value = self._index.value.at[self.slot_ids].set(
                self.start + self.T)

    # -- the leaves a call carries through its layers --------------------------

    def leaf(self, name, shape, dtype):
        """A leaf of the cache collection -> its value, for the family to
        carry through its layers and hand back to ``close``."""
        if not self.cached:
            return None
        self._leaves.append(self.module.variable(
            "cache", name, lambda: jnp.zeros(shape, dtype)))
        return self._leaves[-1].value

    def pool(self, name, layers, width, dtype, blocks=None):
        """A pool of ``width`` values a position and layer, ``(layers,
        blocks, block_size, width)``: as many blocks as the tables address
        (``paged.num_blocks``) unless ``blocks`` says otherwise."""
        if not self.cached:
            return None
        paged = self.paged
        return self.leaf(name, (
            layers, paged.num_blocks if blocks is None else blocks,
            paged.block_size, width), dtype)

    def write(self, pool, layer, rows, cells=None):
        """This call's positions' ``rows`` ``(B, T, width)`` or ``(B * T,
        width)`` into ``layer`` of ``pool`` (at ``cells`` where a ring keeps
        its own)."""
        return pool.at[(layer,) + (self.cells if cells is None else cells)
                       ].set(rows.reshape(-1, rows.shape[-1]))

    def gather(self, pool, layer, table=None):
        """The rows' whole table rows of ``layer``, in table order ``(B,
        blocks * block_size, width)``: positions past a row's index and
        trash entries are the reader's to mask."""
        table = self.table if table is None else table
        return pool[layer, table].reshape(self.B, -1, pool.shape[-1])

    def key_positions(self):
        """The position of every row ``gather`` returns ``(B, S)``: table
        order is position order.  Without a cache, the call's own."""
        if not self.cached:
            return self.positions
        span = self.table.shape[1] * self.paged.block_size
        return jnp.broadcast_to(jnp.arange(span)[None], (self.B, span))

    def gqa(self, pool, layer, q, k, v, *, mask, lengths, paths, table=None,
            cells=None, window=None):
        """Grouped-query attention of ``q`` ``(B, T, Hkv, G, D)`` over a
        pool whose row holds K then V: this call's ``k``, ``v`` ``(B, T,
        Hkv, D)`` are written, then a decode step reads the pool where it
        lies through the block-table kernel wherever
        ``paged_attention.supported`` says it runs (up to ``lengths``, and
        from ``window`` positions before them on where given: a ring), and
        every other call gathers the table's rows and attends under
        ``mask`` ``(B, T, S)``.  ``paths`` names the (gather, kernel) pair on
        record; ``table`` and ``cells`` are the ring's where the layer has
        one.  -> the context ``(B, T, H * D)`` and the pool.  Without a
        cache the call's own ``k``, ``v`` are attended under ``mask``."""
        cfg = self.module.cfg
        if pool is None:
            return gqa_attend(cfg, q, k, v, mask), None
        B, T = self.B, self.T
        half = pool.shape[-1] // 2
        table = self.table if table is None else table
        pool = self.write(pool, layer, jnp.concatenate(
            [k.reshape(B * T, half), v.reshape(B * T, half)], axis=-1), cells)
        if paged_attention.supported(
                query_len=T, block_size=self.paged.block_size, width=half,
                pool_dtype=pool.dtype, compute_dtype=q.dtype,
                mesh=self.module.mesh, data_shards=self.paged.data_shards,
                groups=q.shape[3]):
            paged_attention.note_path(paths[1])
            ctx = paged_attention.paged_decode_attention(
                q, pool, None, table, lengths, layer=layer,
                firsts=None if window is None else jnp.maximum(
                    lengths - window, 0))
            return ctx.reshape(B, T, -1), pool
        paged_attention.note_path(paths[0])
        rows = self.gather(pool, layer, table)
        shape = (B, rows.shape[1]) + k.shape[2:]
        return gqa_attend(cfg, q, rows[..., :half].reshape(shape),
                          rows[..., half:].reshape(shape), mask), pool

    # -- the end of the call ---------------------------------------------------

    def close(self, *leaves, counts=None):
        """The carried ``leaves`` go back, in the order they were declared,
        and the expert layers' rows ``counts`` are added to ``moe_counts``."""
        for variable, value in zip(self._leaves, leaves):
            variable.value = value
        if self.cached and counts is not None:
            self._counts.value = self._counts.value + counts.reshape(
                self._counts.value.shape)

    def head(self, params, x):
        """The residual stream -> float32 logits: the final norm, rounded
        once, and the untied head's product."""
        cfg = self.module.cfg
        x = rms_norm(x, params["final_norm"]["scale"],
                     cfg.rms_norm_eps).astype(cfg.dtype)
        return dot("btd,dv->btv", x, params["head"]["kernel"])


# -- a long context, walked through the table ----------------------------------

# Context positions a step of the walk takes (whole blocks).
CONTEXT_CHUNK = 1024
_MASKED = -1e30  # finite: exp(_MASKED - m) is exactly 0, no inf - inf


class ContextWalk:
    """A cached call's context read ``pages`` blocks a step through the
    block table, as far as the longest row that counts reaches and no
    further: what a learned selection scores its index keys by
    (``index_scores``) and a prefill chunk attends under a mask by
    (``masked_attention``), without a table row gathered whole.  Built
    once a call, before ``view.advance()``.  ``tables`` is the view's,
    padded with the trash block to whole steps; ``span`` the positions it
    addresses; ``causal`` ``(B, T, span)`` True where a key is not after
    its query."""

    def __init__(self, view: PagedCall):
        bs, tables = view.paged.block_size, view.table
        self.B, self.T, self.block_size = view.B, view.T, bs
        self.pages = min(CONTEXT_CHUNK // bs, tables.shape[1])
        self.chunk = self.pages * bs
        steps_max = -(-tables.shape[1] // self.pages)
        self.tables = jnp.pad(tables, (
            (0, 0), (0, steps_max * self.pages - tables.shape[1])))
        self.span = steps_max * self.chunk
        reach = jnp.max(view.lengths)
        self.steps = jnp.minimum(
            (reach + self.chunk - 1) // self.chunk, steps_max)
        self.causal = (jnp.arange(self.span)[None, None, :]
                       <= view.positions[:, :, None])             # (B, T, S)

    def context(self, pool, layer, j):
        """Positions ``j * chunk .. (j + 1) * chunk - 1`` of every row,
        through the table: ``(B, chunk, width)``."""
        blocks = lax.dynamic_slice_in_dim(
            self.tables, j * self.pages, self.pages, 1)
        return pool[layer, blocks].reshape(
            self.B, self.chunk, pool.shape[-1])

    def index_scores(self, pool, layer, q_i, w):
        """``I[t, s]`` over the rows' cached index keys ``(B, T, span)``,
        -inf where ``s`` is after ``t`` (and past the walk)."""
        def one(j, scores):
            return lax.dynamic_update_slice_in_dim(
                scores, index_scores(q_i, w, self.context(pool, layer, j)),
                j * self.chunk, axis=2)

        scores = lax.fori_loop(0, self.steps, one, jnp.full(
            (self.B, self.T, self.span), -jnp.inf, jnp.float32))
        return jnp.where(self.causal, scores, -jnp.inf)

    def masked_attention(self, cfg, sizes, p, pool, layer, q_n, q_r, mask):
        """Expanded latent attention under ``mask`` ``(B, T, span)`` over
        the cached context, a chunk a step under an online softmax ->
        ``(B, T, H * v)``."""
        B, T, dt = self.B, self.T, cfg.dtype
        H, vd = sizes.heads, sizes.v_head_dim
        rank, lw = sizes.kv_lora_rank, sizes.latent_width

        def one(j, carry):
            m, l, acc = carry
            rows = self.context(pool, layer, j)
            s, v = mla_expanded_scores(cfg, sizes, p, q_n, q_r,
                                       rows[..., :rank], rows[..., rank:lw])
            s = jnp.where(lax.dynamic_slice_in_dim(
                mask, j * self.chunk, self.chunk, 2)[:, None], s, _MASKED)
            m_next = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha, pr = jnp.exp(m - m_next), jnp.exp(s - m_next)
            l = alpha * l + jnp.sum(pr, axis=-1, keepdims=True)
            acc = alpha * acc + dot("bhts,bshv->bhtv", pr.astype(dt), v)
            return m_next, l, acc

        _, l, acc = lax.fori_loop(0, self.steps, one, (
            jnp.full((B, H, T, 1), _MASKED, jnp.float32),
            jnp.zeros((B, H, T, 1), jnp.float32),
            jnp.zeros((B, H, T, vd), jnp.float32)))
        return (acc / l).astype(dt).transpose(0, 2, 1, 3).reshape(
            B, T, H * vd)

    def cells_of(self, chosen):
        """Positions ``(B, k)`` -> their pool cells, (blocks, offsets),
        through the table once."""
        bs = self.block_size
        return (jnp.take_along_axis(self.tables, chosen // bs, 1),
                chosen % bs)


# -- what the engine and the scheduler ask of a decoder family -----------------

def serve_refusals(pools: str, **own) -> Dict[str, str]:
    """Scheduler features a paged-only family cannot serve yet, each with
    its reason: the scheduler refuses them at construction
    (``ContinuousScheduler``), ``decoder_workload`` the ``tensor`` mesh
    before an engine exists, ``PagedCall`` a ``paged`` that asks for them.
    ``pools`` is the family's noun for what it caches; ``own`` replaces a
    reason where the family has a better one.  Whether a feature is built
    for a family or goes (ROADMAP D12) is decided here, a key a feature."""
    return {
        "dense_cache": (
            f"{pools} (cache_mode='paged' only): there is no dense-row "
            "layout of it"),
        "kv_dtype": (
            f"{pools}, stored in the compute type: an int8 or cast cache "
            "needs its own scale layout and a dequantizing read"),
        "per_shard_kv": (
            f"{pools}, replicated: per-shard pools are not built for it"),
        "slo_scheduling": (
            "host tiering swaps the K and V pools block by block and does "
            f"not know {pools}; preempting would lose a victim's cache"),
        "spec_k": (
            f"speculative verify over {pools} (a k+1-wide forward with "
            "roll-back) is not built or tested"),
        "prefix_cache": (
            f"sharing blocks of {pools} between requests is not tested yet"),
        "tensor_mesh": (
            f"{pools} and the expert stack have no tensor rule: serve on a "
            "mesh without a 'tensor' axis"),
        **own,
    }


def decoder_workload(name, module_cls, cfg, mesh, geometry, refusals,
                     served_dtypes=None, *, batch_size: int = 8,
                     seq_len=None, **_unused) -> Workload:
    """A paged-only family's ``Workload``: ``module_cls(cfg, mesh=mesh)``
    under the language-model loss on synthetic tokens, the replicated
    cache's rules, ``geometry(cfg, paged)`` and the family's ``refusals``
    (a mesh with a ``tensor`` axis is refused here, with its reason)."""
    if mesh is not None and mesh.shape.get("tensor", 1) > 1:
        raise ValueError(
            f"{name} on a mesh with tensor={mesh.shape['tensor']}: "
            f"{refusals['tensor_mesh']}")
    seq = seq_len or min(cfg.max_position_embeddings, 128)
    module = module_cls(cfg, mesh=mesh)
    data = functools.partial(synthetic_lm, seq_len=seq,
                             vocab_size=cfg.vocab_size)
    return Workload(
        name=name,
        module=module,
        loss_fn=functools.partial(lm_loss, module),
        init_batch={"tokens": np.zeros((2, seq), np.int32)},
        data_fn=lambda per_host_bs: data(batch_size=per_host_bs),
        eval_data_fn=lambda per_host_bs: data(batch_size=per_host_bs,
                                              holdout=True),
        rules=ShardingRules(),
        batch_size=batch_size,
        clip_grad_norm=1.0,
        learning_rate=3e-4,
        example_key="tokens",
        init_key="tokens",
        cache_rules=cache_rules,
        cache_geometry=functools.partial(geometry, cfg),
        serve_refusals=dict(refusals),
        served_dtypes=(None if served_dtypes is None
                       else functools.partial(served_dtypes, cfg)),
    )
