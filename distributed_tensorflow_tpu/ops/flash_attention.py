"""Flash attention (forward + backward) as Pallas TPU kernels.

Why a kernel at all: XLA materializes the (T, T) score matrix in HBM for the
naive einsum formulation; the flash formulation streams K/V blocks through
VMEM with an online softmax, so HBM traffic is O(T·D) and the score tile
lives entirely on-chip feeding the MXU.  (The reference's equivalent layer is
fused CUDA attention inside TF's binary — SURVEY.md §2 L0.)

Design (round-4 schedule — FlashAttention-2 style grid streaming):

- All three kernels run a 3-D grid ``(batch·heads, outer block, inner
  block)`` where the INNER grid dimension streams the loop operand through
  VMEM in blocks — no kernel keeps a full-T window resident.  That is what
  lifts the old T≤6144 cap: the previous backward kept (T, D) q/o/g and a
  (T, 128) lse window per program, which exceeded VMEM at T=8192·H=16
  (measured: "scoped allocation 16.50M > 16.00M" on v5e).  Per-row running
  statistics (m, l) and the f32 accumulators live in VMEM scratch, which on
  TPU persists across sequential grid steps; they are initialized when the
  inner index is 0 and finalized on its last value.
- Head layout: q/k/v arrive as (B, T, H, D) and are transposed to
  (B·H, T, D) for the kernels.  A transpose-free layout (viewing
  (B, T, H·D) and selecting each head's D-slice via BlockSpec index maps)
  was attempted and is impossible under Mosaic's tiling rule —
  the last block dim must be 128-divisible or equal to the full array dim,
  and a D=64 lane slice is neither (lowering rejects it).  See
  ``_to_heads`` for the measurement note.
- Forward: inner dim streams key blocks.  Causal masking is positional
  inside the tile; key blocks entirely above the diagonal skip their
  compute via ``pl.when`` (their DMAs still run — the schedule trade for
  streaming).
- Key padding masks (``kv_mask``, the reference stack's per-op
  ``attention_mask`` input derived from BERT's ``input_mask``): a (B, Tk)
  validity row, blocked to the key tile; masked keys' probabilities are
  zeroed via s = -inf.  Only KEYS are masked (TF semantics).
- Backward (no atomics): two kernels.
  * dQ: inner dim streams key blocks; recomputes P = exp(S − LSE) per tile
    from the stored LSE (no (T,T) buffer anywhere).
  * dK/dV: inner dim streams QUERY blocks (q/o/g/lse arrive (block_q, ·)
    at a time); each program owns one key block's dk/dv tile.
  Both use dS = P ∘ (dP − Δ) · scale with Δ = rowsum(dO ∘ O): the streaming
  kernels compute Δ from the saved output per q tile; for the resident
  kernels XLA computes it once a call, one f32 a query, and both take it
  as the (1, T) row the log-sum-exp is (``o`` is not their operand).
- The resident schedule (every T whose windows fit VMEM, which is every
  shape a cell runs) computes the causal triangle and little more
  (``schedule``): tiles wholly under the diagonal take no mask at all; the
  tile the diagonal crosses is walked in 128-wide pieces (dQ: 256),
  statically unrolled, each against only the rows at or under it, and only
  the squares the diagonal itself crosses take a mask (a local
  ``row >= col`` compare, hoisted).  At T = 1024 that is 0.5625 of T² (dQ:
  0.625) where whole 512-tiles were 0.75 (the triangle is 0.5).  The
  forward updates its running maximum and sum once a tile, not once a
  piece: a (rows, 1) column costs as many vregs as a (rows, 128) piece.  Non-causal calls and
  T <= 128 keep one body a tile.  What the inputs show is not needed is
  not computed: the -inf guards of the running maximum only under a
  ``kv_mask`` (no other row can be without a valid key), and ``scale`` is
  folded into q (or k, and the (block, D) result) once a block where that
  is exact (a power of two, as 1/8 for head size 64), else it is applied
  to the f32 tile.
- The resident dK/dV kernel builds its score tile transposed, sᵀ = k · qᵀ:
  pᵀ and dsᵀ are then the left operands of plain products (dV += pᵀ · dO,
  dK += dsᵀ · q), and the log-sum-exp and Δ are wanted as the rows they
  are stored as, broadcast along sublanes; no column is made in its loop.
- Attention-probability dropout (the reference models' training recipe —
  TF's fused attention keeps it; round 3 silently dropped it on the flash
  path): implemented IN-KERNEL with the TPU PRNG
  (``pltpu.prng_seed``/``prng_random_bits``), seeded per
  (batch·head, q-block, k-block) tile so forward and both backward kernels
  regenerate the identical keep mask.  Dropout follows softmax semantics:
  the denominator l accumulates UN-dropped probabilities; only the P·V
  (and matching dV/dP backward) contractions see the dropped, 1/(1-rate)
  rescaled probabilities.
- ``flash_attention_with_lse`` returns (out, lse) and is differentiable in
  BOTH outputs: ∂lse/∂s = P, so the lse cotangent folds into the backward
  kernels as dS = P ∘ (dP − Δ + g_lse) · scale.  This is the building block
  ring attention consumes per key block.  Dropout composes exactly with
  the ring combine (l/lse always use undropped probabilities), so the
  with_lse path supports it too — each block pair seeded distinctly.
- What a remat'd layer keeps.  A layer under ``remat`` would run the
  forward kernel a second time in the backward pass, only to rebuild the two
  residuals the backward kernels need of it: the output and the
  log-sum-exp.  Per byte they are the dearest part of a layer to make again
  (the kernel runs far under its roofline where the layer's matmuls run near
  half the peak), so the forward rules name them (``flash_out``,
  ``flash_lse``) and the models' remat takes ``REMAT_POLICY``, which saves
  those names and nothing else: q, k, v and the rest of the layer are still
  recomputed.  The dense fallback has nothing under the names and keeps
  whole-layer remat.
- The log-sum-exp is one f32 a query in HBM: a (1, T) row a head, which the
  forward kernel writes (``_row``: a transpose in VMEM of the (block_q, 1)
  column its tile has) and the backward kernels read by (1, block_q)
  blocks; dQ turns it and Δ into columns once a query block (``_col``),
  dK/dV takes them as they lie.  Broadcast over the 128 lanes, as
  the kernels once wrote it, it was 67 MB a call for GPT-2 medium at
  8 x 1024, four times the attention output and half of what the three
  calls moved through HBM, and far too much to keep from one pass to the
  other.
- Non-TPU platforms take the dense XLA path with identical numerics (f32
  softmax); its backward is XLA autodiff.  Its dropout uses ``jax.random``
  — same distribution, different mask realization than the kernel PRNG
  (documented, tested for moments).  On a TPU the dense path is taken only
  for shapes the kernel cannot tile, and then it says so: one WARNING per
  shape with the reason (``_supported``) — a caller who asked for the
  kernel never gets the (T, T) buffer without a word.
- Under a mesh (``mesh=``) the kernel runs inside a ``shard_map`` over the
  batch axes (``data``, ``fsdp``) and, for heads, ``tensor``: Mosaic
  kernels cannot be partitioned automatically, so a bare ``pallas_call``
  handed to GSPMD on more than one chip is refused by the compiler.
"""

from __future__ import annotations

import functools
import logging
import math
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

logger = logging.getLogger(__name__)

# Block sizes, measured on a v5e at GPT-2's shape (8 x 1024 x 16 x 64, causal,
# bf16; PR 43, per call of 128 heads): with 1024 a sequence of 1024 is ONE
# block a head, its whole triangle walked in 128-wide pieces and nothing
# looped: forward 0.276 ms, dQ 0.439 (0.384 with DQ_PIECE), dK/dV 0.441.  The
# same kernels (dQ in 128-pieces, as in the rest of this comment) in 512
# blocks (one unmasked 512 x 512 tile in a loop beside two walked ones) take
# 0.467, 0.528 and 0.576, in 256 blocks 0.819, 0.855 and 0.800; the whole
# 512 x 512 tiles they replaced took 0.592, 0.597 and 0.866.  A (512, 512)
# f32 tile is 256 vregs of a file of 64; a (rows, 128) piece is worked
# through while the next one's product is in the MXU.  Shorter sequences
# clamp to T (`_fit_block`), so small models are unaffected; a longer one
# gets 1024-blocks, whose unmasked tiles are whole (PERF.md, Findings,
# PR 43 has what T = 2048-8192 read).
BLOCK_Q = int(os.environ.get("DTT_FLASH_BLOCK_Q", "1024"))
BLOCK_K = int(os.environ.get("DTT_FLASH_BLOCK_K", "1024"))
LANES = 128  # Mosaic minimum lane tile

# What a remat round the kernel keeps of it: the forward rules name the
# kernel's two results, and the policy saves those names and nothing else.
FLASH_OUT, FLASH_LSE = "flash_out", "flash_lse"
REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    FLASH_OUT, FLASH_LSE)


def _named(out, lse):
    return checkpoint_name(out, FLASH_OUT), checkpoint_name(lse, FLASH_LSE)


def _col(row):
    """In a kernel: a (1, n) row of per-query statistics as the (n, 1)
    column a (block_q, block_k) score tile is broadcast against."""
    return jnp.transpose(jnp.broadcast_to(row, (LANES, row.shape[1])))[:, :1]


def _row(col):
    """In a kernel: an (n, 1) column of per-query statistics as the (1, n)
    row it is stored as: one f32 a query in HBM, not a lane tile of 128."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], LANES)))[:1]


def _fit_block(T: int, want: int):
    """Largest lane-aligned block (multiple of 128, <= want) dividing T;
    None if T has no such divisor.  Keeps seq lens like 768/1152 on the
    flash path when the preferred block doesn't divide them.  T <= 128 is
    a single whole-sequence block (Mosaic pads the sublane dim)."""
    if T <= 128:
        return T
    b = min(want, T)
    b -= b % 128
    while b >= 128:
        if T % b == 0:
            return b
        b -= 128
    return None


def _interpret() -> bool:
    """DTT_PALLAS_INTERPRET=1 runs the kernel in the Pallas interpreter —
    the CPU-test path for kernel logic (real lowering is TPU-only)."""
    return os.environ.get("DTT_PALLAS_INTERPRET", "") == "1"


def _dropout_mask(rng, shape, rate):
    keep = jax.random.bernoulli(rng, 1.0 - rate, shape)
    return keep.astype(jnp.float32) / (1.0 - rate)


def _dense(q, k, v, *, causal, scale, kv_mask=None, dropout_rate=0.0,
           dropout_rng=None):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        T = q.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    if kv_mask is not None:
        scores = jnp.where(
            (kv_mask > 0)[:, None, None, :], scores, -jnp.inf
        )
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        probs = probs * _dropout_mask(dropout_rng, probs.shape, dropout_rate)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def _dense_with_lse(q, k, v, *, causal, scale, kv_mask=None,
                    dropout_rate=0.0, dropout_rng=None):
    """(out, lse) with plain XLA ops — the differentiable fallback for
    ``flash_attention_with_lse`` off-TPU.  lse: (B, H, Tq) f32.

    Dropout follows the softmax-dropout semantics of the kernel path: the
    denominator (and lse) use UNDROPPED probabilities; only the PV
    contraction sees the dropped/rescaled ones — which is exactly what
    makes per-block dropout compose exactly under ring attention's lse
    combine."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        T = q.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
    if kv_mask is not None:
        scores = jnp.where(
            (kv_mask > 0)[:, None, None, :], scores, -jnp.inf
        )
    m = jnp.max(scores, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - m_safe[..., None])
    l = jnp.sum(p, axis=-1)
    lse = m_safe + jnp.log(jnp.maximum(l, 1e-30))
    probs = p / jnp.maximum(l, 1e-30)[..., None]
    if dropout_rate > 0.0 and dropout_rng is not None:
        probs = probs * _dropout_mask(dropout_rng, probs.shape, dropout_rate)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out, lse


def _tile_dropout(seed_ref, b, qi, kj, shape, rate):
    """Regenerate the identical keep/rescale mask for tile (b, qi, kj) in
    any kernel: seed the per-core PRNG with the tile coordinates.  Mosaic
    accepts at most two seed values, so b rides the first (added to the
    user seed — injective over the full int32 program range) and (qi, kj)
    pack into the second (qi/kj < 2^16 blocks, i.e. T < 8.4M — far beyond
    any VMEM-feasible grid)."""
    from jax.experimental.pallas import tpu as pltpu

    pltpu.prng_seed(seed_ref[0] + b, (qi << 16) | kj)
    bits = pltpu.prng_random_bits(shape)  # int32, uniform over 2^32
    # P(keep) = 1 - rate via unsigned threshold compare.
    thresh = np.int32(
        np.uint32(np.round(rate * 2.0**32) - 2**31)
    )  # shift to signed domain
    keep = bits >= thresh
    return jnp.where(keep, 1.0 / (1.0 - rate), 0.0)


def _causal_tile_mask(s, qi, kj, block_q, block_k, transposed=False):
    """``s`` with -inf above the diagonal: a (block_q, block_k) tile of
    q-block ``qi`` and k-block ``kj``, or that tile transposed."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1 if transposed else 0
    )
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0 if transposed else 1
    )
    return jnp.where(q_pos >= k_pos, s, -jnp.inf)


def _fwd_kernel(*refs, causal, scale, block_q, block_k, save_lse,
                has_mask, dropout_rate):
    from jax.experimental import pallas as pl

    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    refs = refs[3:]
    mask_ref = refs.pop(0) if has_mask else None
    seed_ref = refs.pop(0) if dropout_rate > 0.0 else None
    o_ref = refs.pop(0)
    lse_ref = refs.pop(0) if save_lse else None
    acc_ref, m_ref, l_ref = refs[-3:]

    b = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Key blocks entirely above the causal diagonal contribute nothing.
    run = (kj * block_k <= (qi + 1) * block_q - 1) if causal else True

    @pl.when(run)
    def _compute():
        # Keep matmul operands in the input dtype (bf16 in production): the
        # MXU runs bf16 x bf16 -> f32 at full rate.  All accumulation /
        # softmax statistics stay f32 (preferred_element_type).
        q = q_ref[0]  # (block_q, D)
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k) f32
        if causal:
            s = _causal_tile_mask(s, qi, kj, block_q, block_k)
        if has_mask:
            s = jnp.where(mask_ref[0] > 0, s, -jnp.inf)  # (1, block_k)
        m_prev = m_ref[...][:, :1]  # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m_prev), m_prev - m_safe,
                                  -jnp.inf))
        alpha = jnp.where(jnp.isfinite(alpha), alpha, 0.0)
        l_prev = l_ref[...][:, :1]
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # Softmax-dropout semantics: l sees UN-dropped p; only the PV
        # contraction sees the dropped/rescaled probabilities.
        if dropout_rate > 0.0:
            p = p * _tile_dropout(seed_ref, b, qi, kj,
                                  (block_q, block_k), dropout_rate)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_safe, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        if save_lse:
            # Rows with zero valid keys (l == 0) get lse = -1e30, so a
            # downstream exp(lse - anything) underflows to an exact no-op
            # contribution (ring attention's cross-block combine).
            m = m_ref[...][:, :1]
            lse = jnp.where(l > 0, m + jnp.log(l_safe), -1e30)
            lse_ref[0] = _row(lse)


# ---------------------------------------------------------------------------
# Resident-schedule kernels: the whole loop operand (K/V for fwd+dQ; q, dO and
# the two statistics rows for dK/dV) stays in VMEM and the kernel iterates it
# with an in-register fori_loop.  Chosen by `_resident_*_bytes` when the
# windows fit; the streaming kernels above are the long-T schedule.
#
# A causal call computes the triangle and little more (`schedule`): tiles
# wholly under the diagonal take no mask, and the tile the diagonal crosses is
# walked in PIECE-wide pieces, statically unrolled, each against only the rows
# at or under it, with a local `row >= col` compare on the PIECE x PIECE
# squares the diagonal itself crosses and nowhere else.
# ---------------------------------------------------------------------------

# Width of the pieces the diagonal's tile is walked in.  dQ takes wider ones:
# its statistics are (rows, 1) columns, each broadcast across a piece's lanes,
# and at 256 the broadcast serves two lane tiles (measured on a v5e, PR 43,
# 128 heads of 1024 x 64 a call: dQ 0.439 ms in 128-pieces, 0.384 in 256,
# 0.416 in 512; the forward, whose columns are made once a tile, 0.276,
# 0.281, 0.297; dK/dV, which has no column, 0.441, 0.459, 0.547).
PIECE = LANES
DQ_PIECE = 2 * LANES

class Schedule(NamedTuple):
    """What of the (T, T) score matrix one head's resident kernels compute.
    The kernels' loops and the tests both read it; ``share`` is the part of
    T² that is computed (the causal triangle itself is 0.5 + 0.5 / T)."""
    block_q: int
    block_k: int
    piece: int            # 0: the diagonal's tile is not walked
    unmasked_tiles: int   # whole tiles that take no positional mask
    masked_tiles: int     # whole tiles under the positional mask
    diagonal_pieces: int  # pieces of the walked tiles, over all of them
    share: float


def schedule(T: int, block_q: int, block_k: int, causal: bool,
             piece: int = PIECE) -> Schedule:
    """The rule is in what a call shows: causal, square blocks of more than
    one ``piece``.  Everything else (non-causal, T <= 128, blocks that
    differ) keeps one body a tile, under the positional mask where causal."""
    nq, nk = -(-T // block_q), -(-T // block_k)
    if not causal:
        return Schedule(block_q, block_k, 0, nq * nk, 0, 0, 1.0)
    if not (block_q == block_k and block_q % piece == 0
            and block_q > piece):
        tiles = sum(min(((i + 1) * block_q - 1) // block_k + 1, nk)
                    for i in range(nq))
        return Schedule(block_q, block_k, 0, 0, tiles, 0,
                        tiles * block_q * block_k / float(T * T))
    per_tile = block_q // piece
    unmasked = nq * (nq - 1) // 2
    walked = sum(block_q - lo for lo in range(0, block_q, piece)) * piece
    return Schedule(
        block_q, block_k, piece, unmasked, 0, nq * per_tile,
        (unmasked * block_q * block_k + nq * walked) / float(T * T))


_LOGGED_SCHEDULES = set()


def _log_schedule(kernels: str, shape, sched: Schedule):
    """One line a shape, at trace: what the kernels will compute of it."""
    if (kernels, tuple(shape), sched) in _LOGGED_SCHEDULES:
        return
    _LOGGED_SCHEDULES.add((kernels, tuple(shape), sched))
    logger.info(
        "flash attention, %s: shape %s in %dx%d tiles: %d unmasked, %d under "
        "the positional mask, %d diagonal pieces of %d; %.4f of T^2 computed",
        kernels, tuple(shape), sched.block_q, sched.block_k,
        sched.unmasked_tiles, sched.masked_tiles, sched.diagonal_pieces,
        sched.piece, sched.share)


def _exact_scale(scale: float) -> bool:
    """Whether multiplying a bf16 operand by ``scale`` is exact (a power of
    two, as 1/8 for head size 64), so that it can be folded into q or k once
    a block in place of a pass over every f32 score tile."""
    return math.frexp(scale)[0] == 0.5


def _nt(a, b):
    """a · bᵀ over the minor dimension of both, f32 out."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _lower_triangle(n, transposed=False):
    """(n, n) bool: query >= key, queries in rows (in columns if transposed)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return col >= row if transposed else row >= col


def _mask_square(s, under, lo=0):
    """A piece's scores with -inf above the diagonal: only its rows from
    ``lo`` on, as many as ``under`` has, hold the square the diagonal
    crosses; the piece's other rows lie wholly under it."""
    hi = lo + under.shape[0]
    rows = [jnp.where(under, s[lo:hi], -jnp.inf)]
    if lo:
        rows.insert(0, s[:lo])
    if hi < s.shape[0]:
        rows.append(s[hi:])
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)


def _rows_from(x, lo, new):
    """x with its rows from ``lo`` on replaced by ``new``."""
    return new if lo == 0 else jnp.concatenate([x[:lo], new], axis=0)


def _rows_upto(x, hi, new):
    """x with its rows up to ``hi`` replaced by ``new``."""
    return new if hi == x.shape[0] else jnp.concatenate([new, x[hi:]], axis=0)


def _fwd_kernel_resident(*refs, seq_len, sched, causal, scale, save_lse,
                         has_mask, dropout_rate):
    from jax.experimental import pallas as pl

    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    refs = refs[3:]
    mask_ref = refs.pop(0) if has_mask else None
    seed_ref = refs.pop(0) if dropout_rate > 0.0 else None
    o_ref = refs.pop(0)
    lse_ref = refs.pop(0) if save_lse else None
    block_q, block_k, piece = sched.block_q, sched.block_k, sched.piece
    b = pl.program_id(0)
    qi = pl.program_id(1)
    fold = _exact_scale(scale)
    q = q_ref[0]  # (block_q, D)
    if fold:
        q = q * scale
    D = q.shape[-1]
    # A row with no kv_mask sees a key in the first block it is given, so
    # its running maximum is finite from there on; only a kv_mask can leave
    # a row with no valid key, and only then are the -inf guards needed.
    guarded = has_mask

    def step(carry, parts):
        """One online-softmax step over a tile given as its ``parts``, each
        (lo, start, width, keep, drop): keys [start, start + width) against
        the carry's rows from ``lo`` on.  A whole tile is one part; the
        diagonal's tile is its pieces, and the statistics' columns are
        still updated once for the tile, not once a piece (a (rows, 1)
        column is as many vregs as a (rows, 128) piece)."""
        acc, m, l = carry
        scores = []
        for lo, start, width, keep, _ in parts:
            s = _nt(q[lo:], k_ref[0, pl.ds(start, width), :])
            if not fold:
                s = s * scale
            if keep is not None:
                s = keep(s)
            if has_mask:
                s = jnp.where(mask_ref[0, :, pl.ds(start, width)] > 0, s,
                              -jnp.inf)
            scores.append(s)  # (rows, width) f32
        top = scores[0]
        for (lo, *_), s in zip(parts[1:], scores[1:]):
            top = _rows_from(top, lo, jnp.maximum(top[lo:], s))
        m_new = jnp.maximum(m, jnp.max(top, axis=-1, keepdims=True))
        if guarded:
            m_new = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - m_new, -jnp.inf))
            alpha = jnp.where(jnp.isfinite(alpha), alpha, 0.0)
        else:
            alpha = jnp.exp(m - m_new)
        m_wide = m_new
        if len(parts) > 1:
            m_wide = jnp.broadcast_to(m_new, (block_q, width))
        total = pv = None
        for (lo, start, width, _, drop), s in zip(parts, scores):
            p = jnp.exp(s - m_wide[lo:])
            total = p if lo == 0 else _rows_from(total, lo, total[lo:] + p)
            # Softmax-dropout semantics: l sees UN-dropped p; only the PV
            # contraction sees the dropped/rescaled probabilities.
            if drop is not None:
                p = p * drop
            v_blk = v_ref[0, pl.ds(start, width), :]
            x = _nn(p.astype(v_blk.dtype), v_blk)
            pv = x if lo == 0 else _rows_from(pv, lo, pv[lo:] + x)
        l = l * alpha + jnp.sum(total, axis=-1, keepdims=True)
        return acc * alpha + pv, m_new, l

    def tile_drop(j):
        if dropout_rate > 0.0:
            return _tile_dropout(seed_ref, b, qi, j, (block_q, block_k),
                                 dropout_rate)
        return None

    def body(j, carry, positional):
        keep = None
        if positional:
            keep = lambda s: _causal_tile_mask(s, qi, j, block_q, block_k)
        return step(carry, [(0, j * block_k, block_k, keep, tile_drop(j))])

    carry = (jnp.zeros((block_q, D), jnp.float32),
             jnp.full((block_q, 1), -jnp.inf, jnp.float32),
             jnp.zeros((block_q, 1), jnp.float32))
    if piece:
        carry = jax.lax.fori_loop(
            0, qi, functools.partial(body, positional=False), carry)
        # The diagonal's tile: key pieces, each against the rows at or
        # under it; the mask on the square the diagonal crosses.
        keep = functools.partial(_mask_square, under=_lower_triangle(piece))
        drop = tile_drop(qi)
        carry = step(carry, [
            (lo, qi * block_k + lo, piece, keep,
             None if drop is None else drop[lo:, lo:lo + piece])
            for lo in range(0, block_q, piece)])
    else:
        hi = pl.cdiv(seq_len, block_k)
        if causal:
            # highest key block intersecting this q block's causal triangle
            hi = jnp.minimum(((qi + 1) * block_q - 1) // block_k + 1, hi)
        carry = jax.lax.fori_loop(
            0, hi, functools.partial(body, positional=causal), carry)
    acc, m, l = carry
    l_safe = jnp.maximum(l, 1e-30) if guarded else l
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    if save_lse:
        lse = m + jnp.log(l_safe)
        if guarded:
            # Rows with zero valid keys (l == 0) get lse = -1e30, so a
            # downstream exp(lse - anything) underflows to an exact no-op
            # contribution (ring attention's cross-block combine).
            lse = jnp.where(l > 0, lse, -1e30)
        lse_ref[0] = _row(lse)


def _dq_kernel_resident(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, *rest,
                        seq_len, sched, causal, scale, has_mask,
                        dropout_rate):
    from jax.experimental import pallas as pl

    rest = list(rest)
    mask_ref = rest.pop(0) if has_mask else None
    seed_ref = rest.pop(0) if dropout_rate > 0.0 else None
    dq_ref = rest.pop(0)
    block_q, block_k, piece = sched.block_q, sched.block_k, sched.piece
    b = pl.program_id(0)
    qi = pl.program_id(1)
    fold = _exact_scale(scale)
    q = q_ref[0]                              # (block_q, D), input dtype
    if fold:
        q = q * scale
    g = g_ref[0]                              # (block_q, D)
    lse = _col(lse_ref[0])                    # (block_q, 1)
    delta = _col(delta_ref[0])                # Δ − g_lse, (block_q, 1)
    D = q.shape[-1]

    def tile(lo, start, width, keep, drop):
        """What keys [start, start + width) add to dQ's rows from ``lo``
        on: (rows, D) f32, without the scale where that is folded."""
        k_blk = k_ref[0, pl.ds(start, width), :]
        v_blk = v_ref[0, pl.ds(start, width), :]
        s = _nt(q[lo:], k_blk)
        if not fold:
            s = s * scale
        if keep is not None:
            s = keep(s)
        if has_mask:
            s = jnp.where(mask_ref[0, :, pl.ds(start, width)] > 0, s,
                          -jnp.inf)
        p = jnp.exp(s - lse[lo:])             # masked -> exp(-inf) = 0
        dp = _nt(g[lo:], v_blk)
        if drop is not None:
            dp = dp * drop
        ds = p * (dp - delta[lo:])
        if not fold:
            ds = ds * scale
        return _nn(ds.astype(k_blk.dtype), k_blk)

    def tile_drop(j):
        if dropout_rate > 0.0:
            return _tile_dropout(seed_ref, b, qi, j, (block_q, block_k),
                                 dropout_rate)
        return None

    def body(j, dq, positional):
        keep = None
        if positional:
            keep = lambda s: _causal_tile_mask(s, qi, j, block_q, block_k)
        return dq + tile(0, j * block_k, block_k, keep, tile_drop(j))

    dq = jnp.zeros((block_q, D), jnp.float32)
    if piece:
        dq = jax.lax.fori_loop(
            0, qi, functools.partial(body, positional=False), dq)
        keep = functools.partial(_mask_square, under=_lower_triangle(piece))
        drop = tile_drop(qi)
        for lo in range(0, block_q, piece):
            dq = _rows_from(dq, lo, dq[lo:] + tile(
                lo, qi * block_k + lo, piece, keep,
                None if drop is None else drop[lo:, lo:lo + piece]))
    else:
        hi = pl.cdiv(seq_len, block_k)
        if causal:
            hi = jnp.minimum(((qi + 1) * block_q - 1) // block_k + 1, hi)
        dq = jax.lax.fori_loop(
            0, hi, functools.partial(body, positional=causal), dq)
    if fold:
        dq = dq * scale
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel_resident(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         *rest, seq_len, sched, causal, scale, has_mask,
                         dropout_rate):
    """One key block's dK and dV.  The score tile is built transposed,
    sᵀ = k · qᵀ (keys in rows): pᵀ and dsᵀ are then the left operands of
    plain products (dV += pᵀ · dO, dK += dsᵀ · q), dpᵀ = v · dOᵀ has the
    form q · kᵀ has, and the log-sum-exp and Δ are wanted as the
    (1, block_q) rows they are stored as, broadcast along sublanes."""
    from jax.experimental import pallas as pl

    rest = list(rest)
    mask_ref = rest.pop(0) if has_mask else None
    seed_ref = rest.pop(0) if dropout_rate > 0.0 else None
    dk_ref, dv_ref = rest
    block_q, block_k, piece = sched.block_q, sched.block_k, sched.piece
    b = pl.program_id(0)
    ki = pl.program_id(1)
    fold = _exact_scale(scale)
    k = k_ref[0]                              # (block_k, D), input dtype
    v = v_ref[0]                              # (block_k, D)
    k_s = k * scale if fold else k
    D = k.shape[-1]
    if has_mask:
        # The one column this kernel makes, once a key block.
        valid = _col(mask_ref[0, :, pl.ds(ki * block_k, block_k)]) > 0

    def stat_row(ref, start, width):
        """Queries [start, start + width) of a resident (1, T) row.  A
        sequence of one block (T <= 128) need not be lane-aligned, and a
        slice that Mosaic cannot prove aligned is refused: take it whole."""
        if seq_len == width:
            return ref[0]
        return ref[0, :, pl.ds(start, width)]

    def tile(hi, start, width, keep, drop):
        """(dK, dV) of this block's keys up to ``hi`` from queries
        [start, start + width), dK without the scale where that is folded."""
        q_blk = q_ref[0, pl.ds(start, width), :]
        g_blk = g_ref[0, pl.ds(start, width), :]
        s = _nt(k_s[:hi], q_blk)              # (keys, queries)
        if not fold:
            s = s * scale
        if keep is not None:
            s = keep(s)
        if has_mask:
            s = jnp.where(valid[:hi], s, -jnp.inf)
        p = jnp.exp(s - stat_row(lse_ref, start, width))
        dp = _nt(v[:hi], g_blk)
        p_v = p
        if drop is not None:
            p_v, dp = p * drop, dp * drop
        dv = _nn(p_v.astype(g_blk.dtype), g_blk)          # (P∘M)ᵀ dO
        ds = p * (dp - stat_row(delta_ref, start, width))
        if not fold:
            ds = ds * scale
        return _nn(ds.astype(q_blk.dtype), q_blk), dv     # dSᵀ Q

    def tile_drop(i):
        if dropout_rate > 0.0:
            # Seeded and drawn as the forward drew it, queries in rows.
            return _tile_dropout(seed_ref, b, i, ki, (block_q, block_k),
                                 dropout_rate).T
        return None

    def body(i, carry, positional):
        keep = None
        if positional:
            keep = lambda s: _causal_tile_mask(s, i, ki, block_q, block_k,
                                               transposed=True)
        dk, dv = tile(block_k, i * block_q, block_q, keep, tile_drop(i))
        return carry[0] + dk, carry[1] + dv

    z = jnp.zeros((block_k, D), jnp.float32)
    num_q_blocks = pl.cdiv(seq_len, block_q)
    if piece:
        # The diagonal's tile: query pieces, each against the keys at or
        # under it; then the query blocks wholly under the diagonal.
        under = _lower_triangle(piece, transposed=True)
        drop = tile_drop(ki)
        carry = (z, z)
        for lo in range(0, block_q, piece):
            hi = lo + piece
            new = tile(hi, ki * block_q + lo, piece,
                       functools.partial(_mask_square, under=under, lo=lo),
                       None if drop is None else drop[:hi, lo:hi])
            carry = tuple(_rows_upto(x, hi, x[:hi] + y)
                          for x, y in zip(carry, new))
        carry = jax.lax.fori_loop(
            ki + 1, num_q_blocks, functools.partial(body, positional=False),
            carry)
    else:
        first = (ki * block_k) // block_q if causal else 0
        carry = jax.lax.fori_loop(
            first, num_q_blocks, functools.partial(body, positional=causal),
            (z, z))
    dk, dv = carry
    if fold:
        dk = dk * scale
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# VMEM budget for keeping a kernel's loop windows resident (the windows are
# double-buffered by the pipeline, hence the 2x in the estimates).  16 MB
# VMEM on v5e.  Measured boundary: the dkv windows at T=8192, D=64 (q/o/g
# 3 MB + lse 4 MB, x2 = 14 MB estimate) abort Mosaic ("scoped allocation
# 16.50M > 16.00M"), while the ring path's T=4096+g_lse case (11.5 MB
# estimate) compiles and is +65% over einsum — so the cutoff sits between:
# 13 MB keeps every shape that compiles on the fast resident schedule.
RESIDENT_VMEM_BUDGET = int(
    os.environ.get("DTT_FLASH_RESIDENT_BUDGET", str(13 * 2**20)))


def _resident_kv_bytes(T, D, itemsize):
    return 2 * (2 * T * D * itemsize)  # K + V windows, double-buffered


def _resident_dkv_bytes(T, D, itemsize, has_glse):
    # What the measured boundary above was measured with: windows of q, o
    # and g and a (T, 128) f32 window of lse (+ one of g_lse).  The kernel
    # now keeps q and g and two (1, T) rows (lse, and Δ with g_lse folded
    # in), less than half of this; the estimate stays as it was so that the
    # cutoff stays where a chip has shown both sides of it, and the ring
    # path's g_lse still moves it as it did.
    win = 3 * T * D * itemsize + T * LANES * 4 * (2 if has_glse else 1)
    return 2 * win  # double-buffered


def _to_heads(x):
    """(B, T, H, D) -> (B·H, T, D).

    A transpose-free layout (viewing (B, T, H·D) and selecting the head's
    D-slice in the BlockSpec index map) was attempted and is IMPOSSIBLE
    under Mosaic's tiling rule: the last block dim must be 128-divisible or
    equal to the array dim, and a per-head D=64 lane slice is neither
    (lowering rejects block (1, bq, 64) on array
    (B, T, 1024)).  The transpose is therefore structural for D=64 heads.
    """
    B, T, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _from_heads(x, B, H):
    BH, T, D = x.shape
    return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def _seed_operand(dropout_rng):
    """Fold a JAX PRNG key to the int32 scalar the kernel PRNG consumes."""
    bits = jax.random.bits(dropout_rng, dtype=jnp.uint32)
    return bits.astype(jnp.int32).reshape(1)


def _flash_fwd_tpu(q, k, v, kv_mask, *, causal, scale, save_lse,
                   dropout_rate=0.0, seed=None):
    """Returns out (B,T,H,D), and lse (B, H, T) f32 if save_lse."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    block_q = _fit_block(T, BLOCK_Q)
    block_k = _fit_block(T, BLOCK_K)
    has_mask = kv_mask is not None
    has_dropout = dropout_rate > 0.0
    nq, nk = pl.cdiv(T, block_q), pl.cdiv(T, block_k)
    resident = (_resident_kv_bytes(T, D, q.dtype.itemsize)
                <= RESIDENT_VMEM_BUDGET)

    operands = [_to_heads(q), _to_heads(k), _to_heads(v)]
    if resident:
        grid = (B * H, nq)
        qmap = lambda b, i: (b, i, 0)
        in_specs = [
            pl.BlockSpec((1, block_q, D), qmap),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),  # K resident
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),  # V resident
        ]
        mask_spec = pl.BlockSpec((1, 1, T), lambda b, i: (b // H, 0, 0))
        lse_spec = pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i))
        sched = schedule(T, block_q, block_k, causal)
        _log_schedule("forward and dK/dV", q.shape, sched)
        kernel = functools.partial(
            _fwd_kernel_resident, seq_len=T, sched=sched, causal=causal,
            scale=scale, save_lse=save_lse, has_mask=has_mask,
            dropout_rate=dropout_rate,
        )
        scratch = []
        semantics = ("parallel", "arbitrary")
    else:
        grid = (B * H, nq, nk)
        qmap = lambda b, i, j: (b, i, 0)
        kmap = lambda b, i, j: (b, j, 0)
        in_specs = [
            pl.BlockSpec((1, block_q, D), qmap),
            pl.BlockSpec((1, block_k, D), kmap),
            pl.BlockSpec((1, block_k, D), kmap),
        ]
        mask_spec = pl.BlockSpec((1, 1, block_k),
                                 lambda b, i, j: (b // H, 0, j))
        lse_spec = pl.BlockSpec((1, 1, block_q),
                                lambda b, i, j: (b, 0, i))
        kernel = functools.partial(
            _fwd_kernel, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, save_lse=save_lse,
            has_mask=has_mask, dropout_rate=dropout_rate,
        )
        scratch = [
            pltpu.VMEM((block_q, D), jnp.float32),      # acc
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running denom
        ]
        semantics = ("parallel", "parallel", "arbitrary")
    if has_mask:
        # The leading singleton keeps the block's sublane dim tileable (a
        # 2-D (1, Tk) block would have an un-tileable sublane dim of 1).
        in_specs.append(mask_spec)
        operands.append(kv_mask.astype(jnp.int32).reshape(B, 1, T))
    if has_dropout:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(seed)
    out_specs = [pl.BlockSpec((1, block_q, D), qmap)]
    out_shape = [jax.ShapeDtypeStruct((B * H, T, D), q.dtype)]
    if save_lse:
        # One f32 a query, as a (1, T) row a head (the singleton keeps
        # the block's sublane dim tileable, as for the mask).
        out_specs.append(lse_spec)
        out_shape.append(jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
        ),
        interpret=_interpret(),
        name="flash_fwd",
    )(*operands)
    out = _from_heads(res[0], B, H)
    if save_lse:
        return out, res[1].reshape(B, H, T)
    return out, None


def _bwd_dq_kernel(*refs, causal, scale, block_q, block_k,
                   has_mask, has_glse, dropout_rate):
    from jax.experimental import pallas as pl

    refs = list(refs)
    q_ref, k_ref, v_ref, o_ref, g_ref, lse_ref = refs[:6]
    refs = refs[6:]
    glse_ref = refs.pop(0) if has_glse else None
    mask_ref = refs.pop(0) if has_mask else None
    seed_ref = refs.pop(0) if dropout_rate > 0.0 else None
    dq_ref = refs.pop(0)
    dq_acc_ref = refs[-1]

    b = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    run = (kj * block_k <= (qi + 1) * block_q - 1) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0]                          # (block_q, D), input dtype
        g = g_ref[0]                          # (block_q, D)
        o = o_ref[0]                          # (block_q, D)
        lse = _col(lse_ref[0])                # (block_q, 1)
        delta = jnp.sum(                      # Δ = rowsum(dO ∘ O), f32
            g.astype(jnp.float32) * o.astype(jnp.float32),
            axis=-1, keepdims=True,
        )
        if has_glse:
            # dS gains + g_lse ∘ P (∂lse/∂s = P): fold into Δ subtraction.
            delta = delta - _col(glse_ref[0])
        k_blk = k_ref[0]
        v_blk = v_ref[0]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            s = _causal_tile_mask(s, qi, kj, block_q, block_k)
        if has_mask:
            s = jnp.where(mask_ref[0] > 0, s, -jnp.inf)
        p = jnp.exp(s - lse)                  # masked -> exp(-inf) = 0
        dp = jax.lax.dot_general(
            g, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                     # (block_q, block_k)
        if dropout_rate > 0.0:
            dp = dp * _tile_dropout(seed_ref, b, qi, kj,
                                    (block_q, block_k), dropout_rate)
        ds = p * (dp - delta) * scale
        dq_acc_ref[...] += jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, causal, scale, block_q, block_k,
                    has_mask, has_glse, dropout_rate):
    from jax.experimental import pallas as pl

    refs = list(refs)
    q_ref, k_ref, v_ref, o_ref, g_ref, lse_ref = refs[:6]
    refs = refs[6:]
    glse_ref = refs.pop(0) if has_glse else None
    mask_ref = refs.pop(0) if has_mask else None
    seed_ref = refs.pop(0) if dropout_rate > 0.0 else None
    dk_ref, dv_ref = refs[0], refs[1]
    dk_acc_ref, dv_acc_ref = refs[-2:]

    b = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    # Query blocks entirely above this key block's causal wedge skip.
    run = ((qi + 1) * block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _compute():
        k = k_ref[0]                          # (block_k, D), input dtype
        v = v_ref[0]                          # (block_k, D)
        q_blk = q_ref[0]                      # (block_q, D)
        g_blk = g_ref[0]
        o_blk = o_ref[0]
        lse = _col(lse_ref[0])
        delta = jnp.sum(
            g_blk.astype(jnp.float32) * o_blk.astype(jnp.float32),
            axis=-1, keepdims=True,
        )
        if has_glse:
            delta = delta - _col(glse_ref[0])
        s = jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                             # (block_q, block_k)
        if causal:
            s = _causal_tile_mask(s, qi, ki, block_q, block_k)
        if has_mask:
            s = jnp.where(mask_ref[0] > 0, s, -jnp.inf)
        p = jnp.exp(s - lse)
        if dropout_rate > 0.0:
            drop = _tile_dropout(seed_ref, b, qi, ki,
                                 (block_q, block_k), dropout_rate)
            p_v = p * drop                    # what the PV contraction saw
        else:
            p_v = p
        # dV += (P∘M)^T dO
        dv_acc_ref[...] += jax.lax.dot_general(
            p_v.astype(g_blk.dtype), g_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            g_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if dropout_rate > 0.0:
            dp = dp * drop
        ds = p * (dp - delta) * scale
        # dK += dS^T Q
        dk_acc_ref[...] += jax.lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd_tpu(q, k, v, o, lse, g, kv_mask, g_lse, *, causal, scale,
                   dropout_rate=0.0, seed=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D = q.shape
    block_q = _fit_block(T, BLOCK_Q)
    block_k = _fit_block(T, BLOCK_K)
    has_mask = kv_mask is not None
    has_glse = g_lse is not None
    has_dropout = dropout_rate > 0.0
    nq, nk = pl.cdiv(T, block_q), pl.cdiv(T, block_k)
    qh, kh, vh, gh = _to_heads(q), _to_heads(k), _to_heads(v), _to_heads(g)
    mask_op = (kv_mask.astype(jnp.int32).reshape(B, 1, T)
               if has_mask else None)
    # lse and its cotangent arrive (B, H, T): one (1, T) row a head.
    lse = lse.reshape(B * H, 1, T)
    if has_glse:
        g_lse = g_lse.astype(jnp.float32).reshape(B * H, 1, T)

    itemsize = q.dtype.itemsize
    dq_resident = _resident_kv_bytes(T, D, itemsize) <= RESIDENT_VMEM_BUDGET
    dkv_resident = (_resident_dkv_bytes(T, D, itemsize, has_glse)
                    <= RESIDENT_VMEM_BUDGET)
    streaming = dict(causal=causal, scale=scale,
                     block_q=block_q, block_k=block_k,
                     has_mask=has_mask, has_glse=has_glse,
                     dropout_rate=dropout_rate)
    resident = dict(seq_len=T, causal=causal, scale=scale, has_mask=has_mask,
                    dropout_rate=dropout_rate)
    if dq_resident or dkv_resident:
        # Δ = rowsum(dO ∘ O), less the log-sum-exp's cotangent where there
        # is one (∂lse/∂s = P, so dS = P ∘ (dP − Δ + g_lse)): one f32 a
        # query, computed once by XLA from o and g as they arrive and handed
        # to both resident kernels as the row the log-sum-exp is.
        delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1).transpose(0, 2, 1).reshape(B * H, 1, T)
        if has_glse:
            delta = delta - g_lse
    if not (dq_resident and dkv_resident):
        oh = _to_heads(o)          # the streaming kernels make Δ themselves

    # dQ: resident = K/V windows stay in VMEM, fori_loop over key blocks;
    # streaming = grid (B·H, q block, streamed k block).
    if dq_resident:
        qmap = lambda b, i: (b, i, 0)
        full = lambda b, i: (b, 0, 0)
        row = pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i))
        dq_in_specs = [
            pl.BlockSpec((1, block_q, D), qmap),             # q
            pl.BlockSpec((1, T, D), full),                   # k (resident)
            pl.BlockSpec((1, T, D), full),                   # v (resident)
            pl.BlockSpec((1, block_q, D), qmap),             # g
            row,                                             # lse
            row,                                             # delta
        ]
        dq_operands = [qh, kh, vh, gh, lse, delta]
        dq_mask_spec = pl.BlockSpec((1, 1, T), lambda b, i: (b // H, 0, 0))
        dq_sched = schedule(T, block_q, block_k, causal, DQ_PIECE)
        _log_schedule("dQ", q.shape, dq_sched)
        dq_kernel = functools.partial(_dq_kernel_resident, **resident,
                                      sched=dq_sched)
        dq_grid = (B * H, nq)
        dq_out_spec = pl.BlockSpec((1, block_q, D), qmap)
        dq_scratch = []
        dq_semantics = ("parallel", "arbitrary")
    else:
        qmap = lambda b, i, j: (b, i, 0)
        kmap = lambda b, i, j: (b, j, 0)
        row = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
        dq_in_specs = [
            pl.BlockSpec((1, block_q, D), qmap),             # q
            pl.BlockSpec((1, block_k, D), kmap),             # k
            pl.BlockSpec((1, block_k, D), kmap),             # v
            pl.BlockSpec((1, block_q, D), qmap),             # o
            pl.BlockSpec((1, block_q, D), qmap),             # g
            row,                                             # lse
        ]
        dq_operands = [qh, kh, vh, oh, gh, lse]
        if has_glse:
            dq_in_specs.append(row)
            dq_operands.append(g_lse)
        dq_mask_spec = pl.BlockSpec((1, 1, block_k),
                                    lambda b, i, j: (b // H, 0, j))
        dq_kernel = functools.partial(_bwd_dq_kernel, **streaming)
        dq_grid = (B * H, nq, nk)
        dq_out_spec = pl.BlockSpec((1, block_q, D), qmap)
        dq_scratch = [pltpu.VMEM((block_q, D), jnp.float32)]
        dq_semantics = ("parallel", "parallel", "arbitrary")
    if has_mask:
        dq_in_specs.append(dq_mask_spec)
        dq_operands.append(mask_op)
    if has_dropout:
        dq_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dq_operands.append(seed)
    dq = pl.pallas_call(
        dq_kernel,
        grid=dq_grid,
        in_specs=dq_in_specs,
        out_specs=dq_out_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
        scratch_shapes=dq_scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=dq_semantics,
        ),
        interpret=_interpret(),
        name="flash_dq",
    )(*dq_operands)

    # dK/dV: resident = the q and g windows and the two rows stay in VMEM
    # (fori_loop over q blocks); streaming = grid (B·H, k block, streamed q
    # block) — the schedule that lifts the old T<=6144 cap (the resident
    # windows abort Mosaic at T=8192).
    if dkv_resident:
        kv_self = lambda b, ki: (b, ki, 0)
        full = lambda b, ki: (b, 0, 0)
        dkv_in_specs = [
            pl.BlockSpec((1, T, D), full),                   # q (resident)
            pl.BlockSpec((1, block_k, D), kv_self),          # k
            pl.BlockSpec((1, block_k, D), kv_self),          # v
            pl.BlockSpec((1, T, D), full),                   # g (resident)
            pl.BlockSpec((1, 1, T), full),                   # lse (resident)
            pl.BlockSpec((1, 1, T), full),                   # delta (resident)
        ]
        dkv_operands = [qh, kh, vh, gh, lse, delta]
        dkv_mask_spec = pl.BlockSpec((1, 1, T), lambda b, ki: (b // H, 0, 0))
        dkv_kernel = functools.partial(
            _dkv_kernel_resident, **resident,
            sched=schedule(T, block_q, block_k, causal))
        dkv_grid = (B * H, nk)
        dkv_out_specs = [
            pl.BlockSpec((1, block_k, D), kv_self),
            pl.BlockSpec((1, block_k, D), kv_self),
        ]
        dkv_scratch = []
        dkv_semantics = ("parallel", "arbitrary")
    else:
        kv_self = lambda b, ki, i: (b, ki, 0)
        q_stream = lambda b, ki, i: (b, i, 0)
        row = pl.BlockSpec((1, 1, block_q), lambda b, ki, i: (b, 0, i))
        dkv_in_specs = [
            pl.BlockSpec((1, block_q, D), q_stream),         # q
            pl.BlockSpec((1, block_k, D), kv_self),          # k
            pl.BlockSpec((1, block_k, D), kv_self),          # v
            pl.BlockSpec((1, block_q, D), q_stream),         # o
            pl.BlockSpec((1, block_q, D), q_stream),         # g
            row,                                             # lse
        ]
        dkv_operands = [qh, kh, vh, oh, gh, lse]
        if has_glse:
            dkv_in_specs.append(row)
            dkv_operands.append(g_lse)
        dkv_mask_spec = pl.BlockSpec((1, 1, block_k),
                                     lambda b, ki, i: (b // H, 0, ki))
        dkv_kernel = functools.partial(_bwd_dkv_kernel, **streaming)
        dkv_grid = (B * H, nk, nq)
        dkv_out_specs = [
            pl.BlockSpec((1, block_k, D), kv_self),
            pl.BlockSpec((1, block_k, D), kv_self),
        ]
        dkv_scratch = [
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ]
        dkv_semantics = ("parallel", "parallel", "arbitrary")
    if has_mask:
        dkv_in_specs.append(dkv_mask_spec)
        dkv_operands.append(mask_op)
    if has_dropout:
        dkv_in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        dkv_operands.append(seed)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=dkv_grid,
        in_specs=dkv_in_specs,
        out_specs=dkv_out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, T, D), v.dtype),
        ],
        scratch_shapes=dkv_scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=dkv_semantics,
        ),
        interpret=_interpret(),
        name="flash_dkv",
    )(*dkv_operands)

    return (_from_heads(dq, B, H), _from_heads(dk, B, H),
            _from_heads(dv, B, H))


def _platform() -> str:
    return jax.devices()[0].platform


_WARNED_SHAPES = set()


def _supported(q, causal, dropout_rate=0.0):
    """Whether the Pallas kernel runs for this (per-shard) shape.

    Off-TPU the answer is quietly no (the dense path is what the CPU tests
    use).  On a TPU a refusal is logged once per shape at WARNING with the
    reason, so the dense path never hides behind ``--flash_attention``."""
    B, T, H, D = q.shape
    on_tpu = _platform() == "tpu"
    if not on_tpu and not _interpret():
        return False
    if dropout_rate > 0.0 and _interpret():
        # The TPU PRNG (prng_seed/prng_random_bits) has no interpreter
        # lowering; CPU tests of dropout exercise the dense path, the
        # kernel PRNG path is validated on hardware (chip_smoke.py via
        # scripts/validate_tpu.py: validate_kernel_dropout).
        return False
    reason = None
    if _fit_block(T, BLOCK_Q) is None or _fit_block(T, BLOCK_K) is None:
        reason = (f"seq len {T} has no 128-multiple block divisor "
                  f"(<= {BLOCK_Q}/{BLOCK_K})")
    elif not (D in (64, 128, 256) or D % 128 == 0 or _interpret()):
        reason = f"head dim {D} is not 64/128/256 or a multiple of 128"
    if reason is None:
        return True
    if on_tpu and (tuple(q.shape), reason) not in _WARNED_SHAPES:
        _WARNED_SHAPES.add((tuple(q.shape), reason))
        logger.warning(
            "flash attention: DENSE path for shape %s on TPU — %s; the "
            "(T, T) score buffer materializes in HBM", tuple(q.shape), reason)
    return False


def _dense_from_seed(q, k, v, kv_mask, seed, *, causal, scale, dropout_rate):
    """Dense fallback honoring the kernel API's (seed, rate) dropout args:
    same distribution as the in-kernel PRNG, different mask realization."""
    rng = None
    if dropout_rate > 0.0 and seed is not None:
        rng = jax.random.PRNGKey(seed[0].astype(jnp.uint32))
    return _dense(q, k, v, causal=causal, scale=scale, kv_mask=kv_mask,
                  dropout_rate=dropout_rate, dropout_rng=rng)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash(q, k, v, kv_mask, seed, causal, scale, dropout_rate):
    if _supported(q, causal, dropout_rate):
        out, _ = _flash_fwd_tpu(q, k, v, kv_mask, causal=causal, scale=scale,
                                save_lse=False, dropout_rate=dropout_rate,
                                seed=seed)
        return out
    return _dense_from_seed(q, k, v, kv_mask, seed, causal=causal,
                            scale=scale, dropout_rate=dropout_rate)


def _flash_fwd(q, k, v, kv_mask, seed, causal, scale, dropout_rate):
    if _supported(q, causal, dropout_rate):
        out, lse = _flash_fwd_tpu(q, k, v, kv_mask, causal=causal,
                                  scale=scale, save_lse=True,
                                  dropout_rate=dropout_rate, seed=seed)
        out, lse = _named(out, lse)
        return out, (q, k, v, kv_mask, seed, out, lse)
    return (_dense_from_seed(q, k, v, kv_mask, seed, causal=causal,
                             scale=scale, dropout_rate=dropout_rate),
            (q, k, v, kv_mask, seed, None, None))


def _flash_bwd(causal, scale, dropout_rate, res, g):
    q, k, v, kv_mask, seed, o, lse = res
    if o is None:
        # Fallback path (non-TPU / awkward shapes): XLA autodiff of dense,
        # with the SAME seed-derived dropout mask as the fallback forward.
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _dense_from_seed(
                q_, k_, v_, kv_mask, seed, causal=causal, scale=scale,
                dropout_rate=dropout_rate),
            q, k, v,
        )
        return vjp(g) + (None, None)
    dq, dk, dv = _flash_bwd_tpu(q, k, v, o, lse, g, kv_mask, None,
                                causal=causal, scale=scale,
                                dropout_rate=dropout_rate, seed=seed)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_lse(q, k, v, kv_mask, seed, causal, scale, dropout_rate):
    return _flash_fwd_tpu(q, k, v, kv_mask, causal=causal, scale=scale,
                          save_lse=True, dropout_rate=dropout_rate, seed=seed)


def _flash_lse_fwd(q, k, v, kv_mask, seed, causal, scale, dropout_rate):
    out, lse = _flash_fwd_tpu(q, k, v, kv_mask, causal=causal, scale=scale,
                              save_lse=True, dropout_rate=dropout_rate,
                              seed=seed)
    out, lse = _named(out, lse)
    return (out, lse), (q, k, v, kv_mask, seed, out, lse)


def _flash_lse_bwd(causal, scale, dropout_rate, res, cts):
    q, k, v, kv_mask, seed, o, lse = res
    g_out, g_lse = cts
    dq, dk, dv = _flash_bwd_tpu(q, k, v, o, lse, g_out, kv_mask, g_lse,
                                causal=causal, scale=scale,
                                dropout_rate=dropout_rate, seed=seed)
    return dq, dk, dv, None, None


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# Mesh axes the kernel's operands are split over: batch rows over the data
# axes, heads over ``tensor`` (the column-parallel qkv layout hands each
# tensor shard whole heads).  The sequence axis is ring attention's job.
_BATCH_AXES = ("data", "fsdp")
_HEAD_AXIS = "tensor"


class _MeshLayout:
    """How one kernel call's operands split over ``mesh``, and the
    ``shard_map`` that runs it per shard.

    The map is manual over every mesh axis that an enclosing ``shard_map``
    has not already made manual (the pipeline stages are manual over
    ``pipe``; the call nests inside and takes the rest), so no axis is left
    for GSPMD to partition the kernel over."""

    def __init__(self, mesh: Mesh):
        manual = set(jax.sharding.get_abstract_mesh().manual_axes)
        self.mesh = mesh
        self.nested = bool(manual)
        self.free = tuple(a for a in mesh.axis_names if a not in manual)
        self.batch = tuple(a for a in _BATCH_AXES
                           if a in self.free and mesh.shape[a] > 1)
        self.head = (_HEAD_AXIS if _HEAD_AXIS in self.free
                     and mesh.shape[_HEAD_AXIS] > 1 else None)
        self.n_batch = math.prod(mesh.shape[a] for a in self.batch)
        self.n_head = mesh.shape[self.head] if self.head else 1
        b = self.batch or None
        self.qkv = P(b, None, self.head, None)   # (B, T, H, D)
        self.mask = P(b, None)                   # (B, Tk)
        self.lse = P(b, self.head, None)         # (B, H, T)

    def shard_shape(self, q) -> jax.ShapeDtypeStruct:
        """The (B, T, H, D) one shard's kernel call sees."""
        B, T, H, D = q.shape
        return jax.ShapeDtypeStruct(
            (max(1, B // self.n_batch), T, max(1, H // self.n_head), D),
            q.dtype)

    def check_divides(self, q) -> None:
        B, _, H, _ = q.shape
        if B % self.n_batch or H % self.n_head:
            raise ValueError(
                f"flash attention under mesh {dict(self.mesh.shape)}: batch "
                f"{B} and heads {H} must divide over {self.batch}="
                f"{self.n_batch} and {self.head!r}={self.n_head} — the "
                "kernel runs in a shard_map with static per-shard shapes "
                "(raise --batch_size, lower --grad_accum_steps, or shrink "
                "the mesh axis)")

    def shard_seed(self, seed, q_local):
        """Offset the dropout seed per shard: the kernels seed tile b with
        ``seed + b`` for b < local B·H, so stepping by that many per shard
        gives every score tile on the mesh its own PRNG stream."""
        shard = 0
        for a in self.batch + ((self.head,) if self.head else ()):
            shard = shard * self.mesh.shape[a] + lax.axis_index(a)
        return seed + shard * (q_local.shape[0] * q_local.shape[2])

    def run(self, local, operands, in_specs, out_specs):
        out = jax.shard_map(
            local,
            # nested in a manual region only the context mesh is allowed
            mesh=None if self.nested else self.mesh,
            in_specs=tuple(in_specs),
            out_specs=out_specs,
            axis_names=set(self.free),
            check_vma=False,
        )(*operands)
        # An enclosing VMA-checked region (the pipeline's) types q as
        # varying over its manual axes; the unchecked map's results come
        # back untyped, and a custom_vjp rule must return what it was given.
        outer = tuple(jax.typeof(operands[0]).vma)
        if outer:
            out = jax.tree.map(
                lambda x: lax.pcast(x, outer, to="varying"), out)
        return out


def _mesh_layout(q, mesh: Optional[Mesh]) -> Optional[_MeshLayout]:
    """The layout when the call has to be split (more than one device and
    an axis left to go manual over), else None."""
    if mesh is None or mesh.size == 1:
        return None
    lay = _MeshLayout(mesh)
    return lay if lay.free else None


def _with_optional(operands, in_specs, lay, kv_mask, seed):
    """Append the optional mask/seed operands with their specs."""
    if kv_mask is not None:
        operands.append(kv_mask)
        in_specs.append(lay.mask)
    if seed is not None:
        operands.append(seed)
        in_specs.append(P())


def _split_optional(rest, lay, kv_mask, seed, q_local):
    rest = list(rest)
    m_ = rest.pop(0) if kv_mask is not None else None
    s_ = lay.shard_seed(rest.pop(0), q_local) if seed is not None else None
    return m_, s_


def _sharded_fwd(lay, q, k, v, kv_mask, seed, *, save_lse, **kw):
    """``_flash_fwd_tpu`` per shard; lse comes back as (B, H, T)."""

    def local(q_, k_, v_, *rest):
        m_, s_ = _split_optional(rest, lay, kv_mask, seed, q_)
        out, lse = _flash_fwd_tpu(q_, k_, v_, m_, save_lse=save_lse,
                                  seed=s_, **kw)
        return (out, lse) if save_lse else out

    operands, in_specs = [q, k, v], [lay.qkv] * 3
    _with_optional(operands, in_specs, lay, kv_mask, seed)
    return lay.run(local, operands, in_specs,
                   (lay.qkv, lay.lse) if save_lse else lay.qkv)


def _sharded_bwd(lay, q, k, v, o, lse, g, kv_mask, seed, **kw):
    """``_flash_bwd_tpu`` per shard."""

    def local(q_, k_, v_, o_, lse_, g_, *rest):
        m_, s_ = _split_optional(rest, lay, kv_mask, seed, q_)
        return _flash_bwd_tpu(q_, k_, v_, o_, lse_, g_, m_, None, seed=s_,
                              **kw)

    operands = [q, k, v, o, lse, g]
    in_specs = [lay.qkv] * 4 + [lay.lse, lay.qkv]
    _with_optional(operands, in_specs, lay, kv_mask, seed)
    return lay.run(local, operands, in_specs, (lay.qkv,) * 3)


# The custom_vjp sits OUTSIDE the shard_maps: forward and backward are each
# one map with explicit specs, so autodiff never has to carry residuals
# across a (possibly nested) manual region on its own.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_sharded(q, k, v, kv_mask, seed, causal, scale, dropout_rate,
                   mesh):
    return _sharded_fwd(_MeshLayout(mesh), q, k, v, kv_mask, seed,
                        save_lse=False, causal=causal, scale=scale,
                        dropout_rate=dropout_rate)


def _flash_sharded_fwd(q, k, v, kv_mask, seed, causal, scale, dropout_rate,
                       mesh):
    out, lse = _named(*_sharded_fwd(
        _MeshLayout(mesh), q, k, v, kv_mask, seed, save_lse=True,
        causal=causal, scale=scale, dropout_rate=dropout_rate))
    return out, (q, k, v, kv_mask, seed, out, lse)


def _flash_sharded_bwd(causal, scale, dropout_rate, mesh, res, g):
    q, k, v, kv_mask, seed, o, lse = res
    dq, dk, dv = _sharded_bwd(_MeshLayout(mesh), q, k, v, o, lse, g,
                              kv_mask, seed, causal=causal, scale=scale,
                              dropout_rate=dropout_rate)
    return dq, dk, dv, None, None


_flash_sharded.defvjp(_flash_sharded_fwd, _flash_sharded_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Fused attention. q/k/v: (B, T, H, D) -> (B, T, H, D).

    ``kv_mask``: optional (B, Tk) key-validity mask (>0 = real token) — the
    reference stack's per-op ``attention_mask`` input (BERT ``input_mask``
    semantics: masks KEYS only, broadcasting over queries).

    ``dropout_rate``/``dropout_rng``: attention-probability dropout (the
    reference models' regularizer).  On the kernel path the keep mask is
    generated in-kernel by the TPU PRNG, seeded from ``dropout_rng`` per
    score tile, and regenerated identically in the backward kernels.  The
    dense fallback uses ``jax.random`` (same distribution, different mask
    realization).  ``dropout_rate=0`` (default) compiles the dropout-free
    kernels.

    ``mesh``: the mesh the caller's arrays are sharded over.  When the
    kernel runs (TPU, or the interpreter) on more than one device it is
    wrapped in a ``shard_map`` over the batch axes and ``tensor`` (heads);
    the dense path needs no wrapping, GSPMD partitions it.
    """
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    seed = None
    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError("dropout_rate > 0 requires dropout_rng")
        seed = _seed_operand(dropout_rng)
    rate = float(dropout_rate)
    lay = _mesh_layout(q, mesh)
    if lay is not None and _supported(lay.shard_shape(q), causal, rate):
        lay.check_divides(q)
        return _flash_sharded(q, k, v, kv_mask, seed, causal, scale, rate,
                              mesh)
    return _flash(q, k, v, kv_mask, seed, causal, scale, rate)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused attention returning (out, lse); differentiable in both.

    out: (B, T, H, D); lse: (B, H, T) f32 per-row logsumexp of the scaled
    scores.  The building block for ring attention's cross-block combine:
    out_total = Σ_blocks out_b · exp(lse_b − logsumexp_b lse_b) is exact.
    Rows with zero valid keys yield out = 0, lse = -1e30 (an exact no-op
    under that combine).

    Attention-prob dropout composes EXACTLY with that combine because the
    softmax statistics (l, lse) always use UNDROPPED probabilities — only
    the PV contraction sees the dropped/rescaled ones:
    Σ_b exp(lse_b − lse_tot)·out_b = Σ_k P_k·M_k·v_k whether the sum is
    one block or many.  Each block needs its OWN ``dropout_rng`` (the ring
    folds in the global block-pair index) or masks would repeat per pair.
    """
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    seed = None
    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError("dropout_rate > 0 requires dropout_rng")
        seed = _seed_operand(dropout_rng)
    if _supported(q, causal, dropout_rate):
        return _flash_lse(q, k, v, kv_mask, seed, causal, scale,
                          float(dropout_rate))
    return _dense_with_lse(q, k, v, causal=causal, scale=scale,
                           kv_mask=kv_mask, dropout_rate=dropout_rate,
                           dropout_rng=dropout_rng)
