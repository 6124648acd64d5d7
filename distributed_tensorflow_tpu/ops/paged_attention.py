"""Decode attention over the paged KV pool, read where it lies (Pallas, TPU).

The serving path keeps K and V in a lane-dense pool of blocks,
``(layers, num_blocks, block_size, heads * head_dim)``, and a slot finds its
positions through its block-table row (``models/__init__.py: PagedKVConfig``).
The plain way to attend over that is to gather every slot's whole table row
into a contiguous ``(B, max_total_len, heads, head_dim)`` view and contract
it: XLA then re-lays the gathered rows with the head size padded to 128
lanes and reads the padded copy, ``max_total_len`` positions a slot whatever
the request's length.

``paged_decode_attention`` is the same exact softmax attention for the
decode shape (one query position a row) without the view: one kernel call a
layer walks the block table, fetches only the ``ceil(length / block_size)``
blocks a row has, by DMA straight from the pool the program carries (the
pool is never sliced, copied or re-laid), and folds them into an online
softmax.  Heads stay merged in the minor dimension throughout:

- the query row ``(1, H * D)`` becomes a block-diagonal ``(H, H * D)`` matrix
  (row h keeps head h's columns), so ``scores = Qbd @ K_chunk^T`` is one MXU
  contraction over the merged width that yields every head's scores at once,
  bf16 operands, f32 accumulation;
- ``acc += P @ V_chunk`` is ``(H, H * D)``; row h is head h's output in head
  h's columns, and the block diagonal is picked out once at the end.

Sixteen-fold redundant arithmetic on the MXU buys what matters here: the
step is bound by the bytes of K and V, and no array with ``head_dim`` in the
minor dimension exists anywhere.

The same walk serves the grouped-query families (``PagedCall.gqa`` in
``models/paged_call.py``), by what its shapes and one more argument say:

- **grouped heads**: ``q`` ``(B, 1, Hkv, G, D)``.  The ``G`` query heads of a
  K/V head share its columns, so the kernel's query is ``(G, Hkv * D)`` (row
  i holds query head ``g * G + i`` in K/V head g's columns), tiled ``Hkv``
  times down the rows and masked to the block diagonal ``(H, Hkv * D)``:
  fourfold redundant where the form above is sixteenfold;
- **one pool**: the row is ``2 * Hkv * D`` wide, K in the first half and V
  in the second (``v_pool=None``), so one DMA a block brings both;
- **a first position and a ring**: with ``firsts`` a row reads positions
  ``firsts[row] .. lengths[row] - 1`` (a window layer's window).  Logical
  block ``j`` is table entry ``j % max_blocks``, so a table shorter than the
  row is a ring (position ``p`` in ring cell ``p % capacity``); the walk
  starts at the chunk that holds the first position, fetches only the
  blocks that hold one of the row's, and masks both ends.

One algorithm, two implementations: ``supported`` says whether the kernel
runs for a call, from what the call can observe (decode shape, the pool's
storage type, the platform, one device); everything else keeps the gather
paths in ``models/gpt2.py`` and ``models/paged_call.py``, which are also the
references the kernel is tested against (``tests/test_paged_attention.py``).
"""

from __future__ import annotations

import contextlib
import functools
import importlib
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# The module, not the function of the same name that ``ops`` re-exports: the
# platform and interpreter switches are read through it at call time.
_fa = importlib.import_module("distributed_tensorflow_tpu.ops.flash_attention")

# Key positions folded per compute step: one lane tile of scores.  A block
# (16 positions, 32 KB a pool for GPT-2 medium) is too small to be a step of
# its own, so a step takes CHUNK // block_size blocks, each by its own DMA.
CHUNK = 128
# The grouped form's: its one copy a block is half the two pools' copies a
# position, and a step of 512 positions reads a full layer's rows at 611
# GB/s where one of 128 reads 383 (PERF.md Findings, PR 36).
GROUPED_CHUNK = 512
# A step's copies stand one by one in the program's text up to this many
# blocks (the two pools' 8); beyond, a loop goes over groups of this many
# (the grouped form's 32 blocks in 4 turns: within 5% of all 32 written out
# on the chip, at half the time to trace and lower, which every process
# start pays; one block a turn is 25% slower on the chip).
UNROLLED_PAGES = 8
_MASKED = -1e30  # finite: exp(_MASKED - m) is exactly 0, no inf - inf

KERNEL, GATHER = "kernel", "gather"
# The grouped form's calls are on record by the kind of layer they serve
# (``decoder_parts.py``); every path that reads a pool through the kernel:
GQA_KERNEL_PATHS = ("gqa_kernel_window", "gqa_kernel_full")
KERNEL_PATHS = (KERNEL,) + GQA_KERNEL_PATHS

_trace = threading.local()


@contextlib.contextmanager
def record_paths():
    """Collects, while a program is traced, which implementation each call
    of the paged attention chose (``note_path``).  The engine wraps every
    program it jits in this, so the path a program was traced with is on
    record beside its compile count."""
    outer = getattr(_trace, "paths", None)
    _trace.paths = paths = []
    try:
        yield paths
    finally:
        _trace.paths = outer


def note_path(path: str) -> None:
    paths = getattr(_trace, "paths", None)
    if paths is not None:
        paths.append(path)


def one_path(paths) -> str:
    """The path of a program from its calls': the one every call took (the
    kernel's only if all did), else the gather's."""
    return next(iter(paths)) if len(set(paths)) == 1 else GATHER


def program_paths(paths):
    """The paths a program is on record with: ``one_path`` of the kernel's
    two, and where its calls name paths of their own (a family whose
    layers are of two kinds and attend two ways), each of them."""
    kinds = set(paths)
    if not kinds:
        return ()
    if kinds <= {KERNEL, GATHER}:
        return (one_path(paths),)
    return tuple(sorted(kinds))


def supported(*, query_len: int, block_size: int, width: int, pool_dtype,
              compute_dtype, mesh=None, data_shards: int = 1,
              groups: Optional[int] = None) -> bool:
    """Whether the kernel runs for this call: a decode step, a pool stored
    in the compute type (int8 and cast-on-write pools dequantize or convert
    in the gather), one device, and a TPU (or the interpreter).  On the TPU
    a block must also be whole tiles of the pool's type: ``width``, the
    columns of K (and as many of V) in a pool row, whole lane tiles, and in
    the grouped form (``groups`` given) the query heads of a K/V head whole
    float32 sublane tiles where there is more than one."""
    if query_len != 1 or jnp.dtype(pool_dtype) != jnp.dtype(compute_dtype):
        return False
    if data_shards != 1 or (mesh is not None and mesh.size != 1):
        return False
    if (CHUNK if groups is None else GROUPED_CHUNK) % block_size:
        return False
    if _fa._interpret():
        return True
    if _fa._platform() != "tpu":
        return False
    sublanes = 8 * 4 // jnp.dtype(pool_dtype).itemsize
    return (block_size % sublanes == 0 and width % 128 == 0
            and (groups in (None, 1) or groups % 8 == 0))


def _kernel(*refs, scale, chunk_size, block_size, max_blocks, heads, groups,
            pools, windowed):
    """One row of the batch a grid step.  ``refs``: the scalars (layer,
    lengths, tables, and where ``windowed`` the rows' first positions), the
    query, the ``pools`` pools in HBM, the output, then the scratch: a
    fetch buffer a pool, the softmax's running maximum, sum and
    accumulator, the hand-over state, a DMA semaphore pair a pool."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = 3 + windowed
    layer_ref, lengths_ref, tables_ref = refs[:3]
    firsts_ref = refs[3] if windowed else None
    q_ref, hbm, o_ref = refs[n], refs[n + 1:n + 1 + pools], refs[n + 1 + pools]
    scratch = refs[n + 2 + pools:]
    bufs, (m_ref, l_ref, acc_ref, state), sems = (
        scratch[:pools], scratch[pools:pools + 4], scratch[pools + 4:])

    b, rows = pl.program_id(0), pl.num_programs(0)
    pages = chunk_size // block_size
    width = q_ref.shape[-1]
    layer = layer_ref[0]
    length = lengths_ref[b]
    chunks = (length + chunk_size - 1) // chunk_size

    def first_chunk(row):
        """The chunk that holds the row's first position."""
        return firsts_ref[row] // chunk_size if windowed else 0

    def each_copy(row, chunk, slot, act):
        """``act`` on the copy, from each pool, of every block of one chunk
        that holds one of the row's positions: only such blocks are ever
        fetched.  Logical block ``j`` is table entry ``j % max_blocks``: a
        table shorter than the row is a ring.  The blocks stand one by one
        in the program's text up to ``UNROLLED_PAGES`` of them; beyond, a
        loop goes over groups of that many."""
        blocks = (lengths_ref[row] + block_size - 1) // block_size
        if windowed:
            first_block = firsts_ref[row] // block_size

        def copies(p):
            page = chunk * pages + p
            # Kept in bounds where the predicate is false, too.
            block = tables_ref[row * max_blocks + (
                lax.rem(page, max_blocks) if windowed
                else jnp.minimum(page, max_blocks - 1))]
            wanted = page < blocks
            if windowed:
                wanted &= page >= first_block
            offset = p * block_size
            if not isinstance(p, int):
                offset = pl.multiple_of(offset, block_size)
            dst = pl.ds(offset, block_size)
            return (wanted,) + tuple(
                pltpu.make_async_copy(pool.at[layer, block],
                                      buf.at[slot, dst], sem.at[slot])
                for pool, buf, sem in zip(hbm, bufs, sems))

        def group(first, size):
            for wanted, *block_copies in [copies(first + p)
                                          for p in range(size)]:
                @pl.when(wanted)
                def _():
                    for copy in block_copies:
                        act(copy)

        if pages <= UNROLLED_PAGES:
            group(0, pages)
        else:
            assert pages % UNROLLED_PAGES == 0, (chunk_size, block_size)
            lax.fori_loop(0, pages // UNROLLED_PAGES, lambda g, _: group(
                g * UNROLLED_PAGES, UNROLLED_PAGES) or 0, 0)

    def start(row, chunk, slot):
        each_copy(row, chunk, slot, lambda copy: copy.start())

    def wait(row, chunk, slot):
        each_copy(row, chunk, slot, lambda copy: copy.wait())

    @pl.when(b == 0)
    def _():
        state[0] = 0  # the buffer the next chunk to compute lands in
        state[1] = 0  # whether that chunk's fetch has been started
        # Probabilities of positions outside a row's are exactly 0, but
        # 0 x what VMEM held before this call may be NaN: blocks that are
        # not fetched must read as finite.  After this only pool data lands
        # here.
        v_buf = bufs[-1]
        v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(length == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _():
        @pl.when(state[1] == 0)
        def _():
            start(b, first_chunk(b), state[0])
            state[1] = 1

        # The next row that has anything to fetch: its first chunk is
        # started under this row's last.
        following = lax.fori_loop(
            b + 1, rows,
            lambda r, found: jnp.where(
                (found == rows) & (lengths_ref[r] > 0), r, found),
            rows)

        # Row ``h`` of the block-diagonal query keeps the columns of its own
        # K/V head, ``h // groups``.
        head_of_column = lax.broadcasted_iota(
            jnp.int32, (heads, width), 1) // (width * groups // heads)
        head_of_row = lax.broadcasted_iota(jnp.int32, (heads, width), 0)
        own = head_of_column == (
            head_of_row if groups == 1 else head_of_row // groups)
        q = q_ref[...].astype(jnp.float32)                   # (groups, width)
        q = (jnp.broadcast_to(q, (heads, width)) if groups == 1
             else jnp.concatenate([q] * (heads // groups), axis=0))
        q_heads = jnp.where(own, q, 0.0)
        q_heads = q_heads.astype(q_ref.dtype)                # (heads, width)

        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def fold(i, _):
            slot = state[0]
            other = 1 - slot

            @pl.when(i + 1 < chunks)
            def _():
                start(b, i + 1, other)

            @pl.when((i + 1 == chunks) & (following < rows))
            def _():
                start(following, first_chunk(following), other)

            wait(b, i, slot)
            if pools == 2:
                k = bufs[0][slot]                            # (chunk, width)
            else:                      # K and V side by side in one row
                k = bufs[0][slot, :, pl.ds(0, width)]
            s = lax.dot_general(
                q_heads, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (heads, chunk)
            position = i * chunk_size + lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            inside = position < length
            if windowed:
                inside &= position >= firsts_ref[b]
            s = jnp.where(inside, s, _MASKED)
            m_prev = m_ref[...]                              # (heads, 1)
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(
                p, axis=1, keepdims=True)
            m_ref[...] = m_next
            if pools == 2:
                v = bufs[1][slot]
            else:
                v = bufs[0][slot, :, pl.ds(width, width)]
            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            state[0] = other
            return 0

        lax.fori_loop(first_chunk(b), chunks, fold, 0)
        out = acc_ref[...] / l_ref[...]                      # (heads, width)
        out = jnp.where(own, out, 0.0)
        if groups == 1:
            out = jnp.sum(out, axis=0, keepdims=True)
        else:
            out = sum(out[g * groups:(g + 1) * groups]
                      for g in range(heads // groups))
        o_ref[...] = out.astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, tables, lengths, *,
                           layer=None, scale: Optional[float] = None,
                           firsts=None):
    """Exact softmax attention of one query position a row over the row's
    cached positions ``firsts[row] .. lengths[row] - 1`` (from 0 where
    ``firsts`` is None).

    ``q`` is ``(B, 1, H, D)`` over pools as wide as the query, ``(layers,
    num_blocks, block_size, H * D)`` each, or grouped, ``(B, 1, Hkv, G,
    D)``, over ONE pool whose row holds K then V, ``(layers, num_blocks,
    block_size, 2 * Hkv * D)``, with ``v_pool`` None.  ``layer`` is the
    (traced) index of this layer, or None over one layer's own pools
    without the leading dimension.  ``tables`` ``(B, max_blocks)`` int32
    maps a row's logical block ``j`` to the physical one in entry ``j %
    max_blocks``: a row longer than its table is a ring, which must still
    hold every block from ``firsts[row]`` on.  ``lengths`` and ``firsts``
    are ``(B,)`` int32.  A row of length 0 fetches nothing and returns
    zeros.  Returns ``q``'s shape and type.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grouped = q.ndim == 5
    if grouped:
        B, T, kv_heads, groups, D = q.shape
    else:
        (B, T, kv_heads, D), groups = q.shape, 1
    if T != 1:
        raise ValueError(f"decode attention takes one query position, got {T}")
    if grouped != (v_pool is None):
        raise ValueError(
            "grouped queries (B, 1, Hkv, G, D) read one pool of K and V "
            "side by side (v_pool=None); queries (B, 1, H, D) read two")
    sources = [k_pool] if grouped else [k_pool, v_pool]
    if layer is None:
        sources, layer = [pool[None] for pool in sources], 0
    _, _, block_size, row_width = sources[0].shape
    width = kv_heads * D
    if row_width != width * (1 + grouped):
        raise ValueError(
            f"pool width {row_width} is not {1 + grouped} x heads x "
            f"head_dim ({kv_heads} x {D})")
    if scale is None:
        scale = 1.0 / float(D) ** 0.5
    max_blocks = tables.shape[1]
    heads = kv_heads * groups
    chunk_size = GROUPED_CHUNK if grouped else CHUNK
    kernel = functools.partial(
        _kernel, scale=scale, chunk_size=chunk_size, block_size=block_size,
        max_blocks=max_blocks, heads=heads, groups=groups,
        pools=len(sources), windowed=firsts is not None)
    scalars = [jnp.asarray(layer, jnp.int32).reshape(1),
               lengths.astype(jnp.int32),
               tables.astype(jnp.int32).reshape(-1)]
    if firsts is not None:
        scalars.append(firsts.astype(jnp.int32))
    # The kernel's rows: query head ``g * G + i`` in row ``i``, in K/V head
    # ``g``'s columns.
    rows = q.reshape(B, 1, width) if groups == 1 else q.reshape(
        B, kv_heads, groups, D).swapaxes(1, 2).reshape(B, groups, width)
    row = pl.BlockSpec((None, groups, width), lambda b, *_: (b, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B,),
            in_specs=[row] + [pool] * len(sources),
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, chunk_size, row_width), source.dtype)
                for source in sources
            ] + [
                pltpu.VMEM((heads, 1), jnp.float32),      # running maximum
                pltpu.VMEM((heads, 1), jnp.float32),      # running sum
                pltpu.VMEM((heads, width), jnp.float32),  # accumulator
                pltpu.SMEM((2,), jnp.int32),
            ] + [pltpu.SemaphoreType.DMA((2,)) for _ in sources],
        ),
        out_shape=jax.ShapeDtypeStruct(rows.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            # Rows share the fetch buffers and hand the next row's first
            # chunk on: they run in order.
            dimension_semantics=("arbitrary",),
        ),
        interpret=_fa._interpret(),
        name="paged_decode_attn_gqa" if grouped else "paged_decode_attn",
    )(*scalars, rows, *sources)
    if groups > 1:
        out = out.reshape(B, groups, kv_heads, D).swapaxes(1, 2)
    return out.reshape(q.shape)
