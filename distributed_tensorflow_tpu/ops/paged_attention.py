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

One algorithm, two implementations: ``supported`` says whether the kernel
runs for a call, from what the call can observe (decode shape, the pool's
storage type, the platform, one device); everything else keeps the gather
path in ``models/gpt2.py``, which is also the reference the kernel is tested
against (``tests/test_paged_attention.py``).
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
_MASKED = -1e30  # finite: exp(_MASKED - m) is exactly 0, no inf - inf

KERNEL, GATHER = "kernel", "gather"

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
              compute_dtype, mesh=None, data_shards: int = 1) -> bool:
    """Whether the kernel runs for this call: a decode step, a pool stored
    in the compute type (int8 and cast-on-write pools dequantize or convert
    in the gather), one device, and a TPU (or the interpreter).  On the TPU
    a block must also be whole tiles of the pool's type."""
    if query_len != 1 or jnp.dtype(pool_dtype) != jnp.dtype(compute_dtype):
        return False
    if data_shards != 1 or (mesh is not None and mesh.size != 1):
        return False
    if CHUNK % block_size:
        return False
    if _fa._interpret():
        return True
    if _fa._platform() != "tpu":
        return False
    sublanes = 8 * 4 // jnp.dtype(pool_dtype).itemsize
    return block_size % sublanes == 0 and width % 128 == 0


def _kernel(layer_ref, lengths_ref, tables_ref,        # scalar prefetch
            q_ref, k_hbm, v_hbm,                       # inputs
            o_ref,                                     # output
            k_buf, v_buf, m_ref, l_ref, acc_ref, state, k_sem, v_sem,
            *, scale, block_size, max_blocks, heads):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, rows = pl.program_id(0), pl.num_programs(0)
    pages = CHUNK // block_size
    width = q_ref.shape[-1]
    layer = layer_ref[0]
    length = lengths_ref[b]
    chunks = (length + CHUNK - 1) // CHUNK

    def copies(row, chunk, slot):
        """The (predicate, K copy, V copy) of each block of one chunk: only
        the blocks below the row's length are ever fetched."""
        blocks = (lengths_ref[row] + block_size - 1) // block_size
        out = []
        for p in range(pages):
            page = chunk * pages + p
            # Clamped so that the table read stays in bounds where the
            # predicate is false.
            block = tables_ref[row * max_blocks
                               + jnp.minimum(page, max_blocks - 1)]
            dst = pl.ds(p * block_size, block_size)
            out.append((
                page < blocks,
                pltpu.make_async_copy(k_hbm.at[layer, block],
                                      k_buf.at[slot, dst], k_sem.at[slot]),
                pltpu.make_async_copy(v_hbm.at[layer, block],
                                      v_buf.at[slot, dst], v_sem.at[slot]),
            ))
        return out

    def start(row, chunk, slot):
        for wanted, k_copy, v_copy in copies(row, chunk, slot):
            @pl.when(wanted)
            def _():
                k_copy.start()
                v_copy.start()

    def wait(row, chunk, slot):
        for wanted, k_copy, v_copy in copies(row, chunk, slot):
            @pl.when(wanted)
            def _():
                k_copy.wait()
                v_copy.wait()

    @pl.when(b == 0)
    def _():
        state[0] = 0  # the buffer the next chunk to compute lands in
        state[1] = 0  # whether that chunk's fetch has been started
        # Probabilities of positions past a row's length are exactly 0, but
        # 0 x what VMEM held before this call may be NaN: blocks that are
        # not fetched must read as finite.  After this only pool data lands
        # here.
        v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(length == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _():
        @pl.when(state[1] == 0)
        def _():
            start(b, 0, state[0])
            state[1] = 1

        # The next row that has anything to fetch: its first chunk is
        # started under this row's last.
        following = lax.fori_loop(
            b + 1, rows,
            lambda r, found: jnp.where(
                (found == rows) & (lengths_ref[r] > 0), r, found),
            rows)

        head_of_column = lax.broadcasted_iota(
            jnp.int32, (heads, width), 1) // (width // heads)
        own = head_of_column == lax.broadcasted_iota(
            jnp.int32, (heads, width), 0)
        q = q_ref[...].astype(jnp.float32)                   # (1, width)
        q_heads = jnp.where(own, jnp.broadcast_to(q, (heads, width)), 0.0)
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
                start(following, 0, other)

            wait(b, i, slot)
            k = k_buf[slot]                                  # (CHUNK, width)
            s = lax.dot_general(
                q_heads, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (heads, CHUNK)
            position = i * CHUNK + lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(position < length, s, _MASKED)
            m_prev = m_ref[...]                              # (heads, 1)
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(
                p, axis=1, keepdims=True)
            m_ref[...] = m_next
            v = v_buf[slot]
            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            state[0] = other
            return 0

        lax.fori_loop(0, chunks, fold, 0)
        out = acc_ref[...] / l_ref[...]                      # (heads, width)
        o_ref[...] = jnp.sum(jnp.where(own, out, 0.0), axis=0,
                             keepdims=True).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, tables, lengths, *,
                           layer=None, scale: Optional[float] = None):
    """Exact softmax attention of one query position a row over the row's
    first ``lengths[row]`` cached positions.

    ``q`` is ``(B, 1, H, D)``; the pools are ``(layers, num_blocks,
    block_size, H * D)`` with ``layer`` the (traced) index of this layer, or
    one layer's own ``(num_blocks, block_size, H * D)`` with ``layer=None``;
    ``tables`` ``(B, max_blocks)`` int32 maps a row's logical blocks to
    physical ones; ``lengths`` ``(B,)`` int32.  A row of length 0 fetches
    nothing and returns zeros.  Returns ``(B, 1, H, D)`` in ``q``'s type.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    if T != 1:
        raise ValueError(f"decode attention takes one query position, got {T}")
    if layer is None:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    _, _, block_size, width = k_pool.shape
    if width != H * D:
        raise ValueError(f"pool width {width} is not heads x head_dim "
                         f"({H} x {D})")
    if scale is None:
        scale = 1.0 / float(D) ** 0.5
    max_blocks = tables.shape[1]
    kernel = functools.partial(
        _kernel, scale=scale, block_size=block_size, max_blocks=max_blocks,
        heads=H)
    row = pl.BlockSpec((None, 1, width), lambda b, *_: (b, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[row, pool, pool],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, CHUNK, width), k_pool.dtype),
                pltpu.VMEM((2, CHUNK, width), v_pool.dtype),
                pltpu.VMEM((H, 1), jnp.float32),        # running maximum
                pltpu.VMEM((H, 1), jnp.float32),        # running sum
                pltpu.VMEM((H, width), jnp.float32),    # accumulator
                pltpu.SMEM((2,), jnp.int32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, 1, width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # Rows share the fetch buffers and hand the next row's first
            # chunk on: they run in order.
            dimension_semantics=("arbitrary",),
        ),
        interpret=_fa._interpret(),
        name="paged_decode_attn",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      lengths.astype(jnp.int32),
      tables.astype(jnp.int32).reshape(-1),
      q.reshape(B, 1, width), k_pool, v_pool)
    return out.reshape(B, 1, H, D)
