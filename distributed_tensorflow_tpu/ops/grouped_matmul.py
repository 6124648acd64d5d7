"""A gated MLP over rows grouped by expert: each (token, expert) assignment
computed once, an expert's stack read once a row tile and never for an
expert without a row (Pallas, TPU).

A layer of sparse experts holds a stack of gated MLPs, ``gate_up`` ``(held,
2f, d)`` and ``down`` ``(held, f, d)``, and a router that gives each of
``N`` tokens ``k`` experts.  The plain way to apply the stack is every held
expert over every token, weighed by gates that are zero where the router
chose otherwise (``models/decoder_parts.py: expert_layer``, its dense
form): ``held`` products a token where the router asked for ``k * held /
experts``, and every expert's ``3 * f * d`` weights read whether a token
chose it or not.

Here each assignment is one row of a buffer whose rows are grouped by
expert in tiles of ``tm`` rows:

- ``layout`` places the rows, in integers over ``(N, held)``.  Expert
  ``g``'s rows start at a tile boundary and keep their tokens' order; its
  last tile is padded.  So a tile belongs to one expert, an expert with
  rows has ``ceil(rows / tm)`` tiles, an expert without has none, and the
  tiles in use are the first of a static ``tiles``: ``N * min(k, held)``
  rows in the worst case plus at most a tile less one row of padding an
  expert.  **No capacity**: an expert may take all ``N`` tokens.
- ``gated_mlp`` runs two kernels over the tiles in use.  The number of
  tiles in use is the (traced) size of a grid dimension, so a tile past it
  is no step at all, and the expert of a visited tile comes from the
  scalar-prefetched ``tile_group``, so a block of weights is fetched only
  for an expert that has the tile.

  *Gate and up.*  At a tile's first step its rows are picked out of ``x``,
  which stays in the core's memory for the call: a 0/1 matrix ``(tm, N)``
  times ``x`` on the MXU is the chosen tokens' rows to the bit, where a
  gather by XLA would write and read the whole static buffer (113 MB a
  layer of the sixth cell's chunk).  The same matrix picks each row's
  gate.  Then the tile meets blocks ``(tn, d)`` of the expert's gate rows
  and up rows (the two halves of ``gate_up``, by two block specs), and
  ``silu(g) * u`` is written rounded to the compute type: the float32
  ``(rows, 2f)`` intermediate never exists.

  *Down, and back to the tokens.*  The tile ``(tm, f)`` meets blocks ``(f,
  tn)`` of ``down``; each row in use is weighed by its gate in float32 and
  added to its token's row of the result's block ``(N, tn)``, which stays
  in the core's memory while every tile adds to it (the result's blocks
  are the grid's outer dimension).  A token's assignments are added in
  the experts' order, as the dense form sums them.  Nothing the size of
  the static buffer is ever read or written by XLA.

  Both kernels read the whole contraction in one block, so a step is one
  MXU product and there is no accumulator to carry.

The stacks are read where they lie: ``gate_up`` and ``down`` may carry the
model's layers in a leading dimension with ``layer`` the (traced) index of
this one, the way ``ops/paged_attention.py`` reads a pool, because a slice
handed to a kernel is copied first, 0.15-0.6 GB a layer here.

Row tiles follow the call: ``tm = min(128, N rounded up to 16)``.  A
decode step of 16 tokens has one 16-row tile an expert with a row (an
expert gets at most one row a token); a prefill chunk has 128-row tiles.
The kernels are bound by the weights' bytes either way: at 128 rows a tile
a product's operations take half the time of its weights' read.

One algorithm, two implementations: ``supported`` says whether the kernels
run for a call (the platform or the interpreter, one device, whole lane
tiles); off the TPU the same layout feeds two batched ``jnp`` products, a
tile with its expert's gathered stack (``gated_mlp_reference``).  Both are
held to every expert over every token in ``tests/test_glm4_moe_lite.py``.
"""

from __future__ import annotations

import contextlib
import importlib
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

_fa = importlib.import_module("distributed_tensorflow_tpu.ops.flash_attention")

GROUPED, DENSE = "grouped", "dense"

# Rows a tile at most: the MXU's side.  At 128 rows the products of a tile
# take about half the time of its expert's weights' read (v5e: 240
# operations a byte at the ridge, 128 a byte here).
MAX_TILE_ROWS = 128
# Rows a tile at least, and what a tile's rows are a multiple of: one
# sublane tile of the compute type.
ROW_ALIGN = 16
# A block of weights a step, at most: large enough that a step's fixed cost
# (0.35 us) is a few percent of its copy, small enough that two operands,
# twice buffered, stay well inside the core's memory.
BLOCK_BYTES = 4 << 20
VMEM_LIMIT_BYTES = 64 << 20

_trace = threading.local()


@contextlib.contextmanager
def record_forms(forms: dict, kind: str):
    """While a program of ``kind`` is traced inside this, every expert
    layer's choice (``note_form``) is put on record in ``forms`` under
    ``"<kind>/<tokens>"``, the tokens a call of its layers sees (a kind's
    programs differ by them, and the choice may): the form, and the
    grouped buffer's static rows (0 for the dense form).  A launch that
    traces nothing records nothing."""
    outer = getattr(_trace, "target", None)
    _trace.target = (forms, kind)
    try:
        yield forms
    finally:
        _trace.target = outer


def note_form(form: str, tokens: int, rows: int = 0) -> None:
    target = getattr(_trace, "target", None)
    if target is not None:
        forms, kind = target
        forms[f"{kind}/{tokens}"] = (form, rows)


class Layout(NamedTuple):
    """Where each assignment's row lies (``layout``)."""
    tm: int                 # rows a tile
    dest: jax.Array         # (N, held) int32: the row of (token, expert),
                            # -1 where the token is not given the expert
    row_token: jax.Array    # (tiles * tm,) int32: the token of a row
    tile_group: jax.Array   # (tiles,) int32: the expert of a tile
    n_tiles: jax.Array      # (1,) int32: the tiles in use, the first ones
    tile_used: jax.Array    # (tiles,) int32: a tile's rows that are some
                            # assignment's (0 past the tiles in use)

    @property
    def rows(self) -> int:
        return self.row_token.shape[0]


def tile_rows(n: int) -> int:
    return min(MAX_TILE_ROWS, -(-n // ROW_ALIGN) * ROW_ALIGN)


def static_tiles(n: int, k: int, held: int, tm: int) -> int:
    """The most tiles ``n`` tokens' assignments can take: an expert gets at
    most ``n`` rows, all of them at most ``n * min(k, held)``, and every
    expert's last tile may be partly empty."""
    return min(held * -(-n // tm), n * min(k, held) // tm + held)


def layout(assigned, k: int) -> Layout:
    """Rows for the True entries of ``assigned`` ``(N, held)`` (token n is
    given held expert g; at most ``k`` a token), grouped by expert in tiles
    of ``tile_rows(N)``, tokens in order within an expert.  A row past its
    expert's last (padding within a tile in use) names the expert's next
    token or the last of all: it is never read.  Written as comparisons
    and sums over small integer arrays, which XLA fuses into a few
    programs, not as gathers, which it runs one by one."""
    n, held = assigned.shape
    tm = tile_rows(n)
    tiles = static_tiles(n, k, held, tm)
    ones = assigned.astype(jnp.int32)
    upto = jnp.cumsum(ones, axis=0)                      # (N, held), inclusive
    tiles_of = (upto[-1] + tm - 1) // tm                 # (held,)
    tile_end = jnp.cumsum(tiles_of)
    tile_start = tile_end - tiles_of
    dest = jnp.where(assigned, tile_start[None, :] * tm + upto - ones, -1)
    tile = jnp.arange(tiles, dtype=jnp.int32)[:, None]
    # A tile in use is one expert's; a tile past them is nobody's.
    owns = (tile_start[None, :] <= tile) & (tile < tile_end[None, :])

    def of_owner(per_expert):                            # (held,) -> (tiles,)
        return jnp.sum(jnp.where(owns, per_expert[None, :], 0), axis=1)

    first = (tile[:, 0] - of_owner(tile_start)) * tm     # the tile's first
    # row, counted within its expert; the token of the q-th row of an
    # expert: as many tokens come before it as have the expert's running
    # count at q or under.
    q = first[:, None] + jnp.arange(tm, dtype=jnp.int32)[None, :]
    running = jnp.sum(jnp.where(owns[:, None, :], upto[None], 0), axis=2)
    before = jnp.sum(running[:, None, :] <= q[:, :, None], axis=-1,
                     dtype=jnp.int32)                    # (tiles, tm)
    return Layout(tm, dest, jnp.minimum(before, n - 1).reshape(-1),
                  of_owner(jnp.arange(held, dtype=jnp.int32)), tile_end[-1:],
                  jnp.clip(of_owner(upto[-1]) - first, 0, tm))


def _block_columns(total: int, row_bytes: int) -> int:
    """Columns of a weights' block: the most whole lane tiles that divide
    ``total`` and keep the block within ``BLOCK_BYTES`` (one lane tile at
    least); all of a ``total`` that is not whole lane tiles, which only the
    interpreter is given."""
    if total % 128:
        return total
    best = 128
    for tn in range(128, total + 1, 128):
        if total % tn == 0 and tn * row_bytes <= BLOCK_BYTES:
            best = tn
    return best


def supported(*, n: int, d: int, f: int, dtype, mesh=None) -> bool:
    """Whether the kernels run for a call: the TPU (or the interpreter) and
    one device, as for the other kernels; on the TPU the model width and
    the experts' width whole lane tiles, the compute type bfloat16 (a
    16-row tile is one sublane tile of it), and the ``n`` tokens' rows,
    which the first kernel keeps in the core's memory twice over, within a
    quarter of ``VMEM_LIMIT_BYTES`` (1,024 tokens of 6,144 are 25 MB)."""
    if mesh is not None and mesh.size != 1:
        return False
    if _fa._interpret():
        return True
    if _fa._platform() != "tpu":
        return False
    return (d % 128 == 0 and f % 128 == 0
            and jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16)
            and n * d * jnp.dtype(dtype).itemsize <= VMEM_LIMIT_BYTES // 4)


def _operands(a, b):
    # The interpreter's dot runs on the CPU, which has no bfloat16-in,
    # float32-out form: widened there, the same products and sums.
    if _fa._platform() == "cpu":
        return a.astype(jnp.float32), b.astype(jnp.float32)
    return a, b


def _gate_up_kernel(group_ref, layer_ref, x_ref, dest_ref, gates_ref,
                    gate_ref, up_ref, h_ref, row_gate_ref, rows_ref):
    """One tile against one block of its expert's gate rows and up rows.
    The tile's rows are picked out of ``x`` on the MXU at its first block:
    a 0/1 matrix ``(tm, N)``, row r's one at the token whose row for the
    tile's expert r is, times ``x`` is those tokens' rows to the bit (a
    row no token has is zeros); the same matrix picks each row's gate."""
    from jax.experimental import pallas as pl

    del group_ref, layer_ref
    tm = rows_ref.shape[0]
    first_row = pl.program_id(0) * tm

    @pl.when(pl.program_id(1) == 0)
    def _():
        row = first_row + lax.broadcasted_iota(
            jnp.int32, (tm, x_ref.shape[0]), 0)
        mine = row == dest_ref[...]
        pick, x = _operands(mine.astype(x_ref.dtype), x_ref[...])
        rows_ref[...] = jnp.dot(
            pick, x, preferred_element_type=jnp.float32).astype(
                rows_ref.dtype)
        row_gate_ref[...] = jnp.sum(
            jnp.where(mine, gates_ref[...], 0.0), axis=1, keepdims=True)

    contract_minor = (((1,), (1,)), ((), ()))
    rows, gate = _operands(rows_ref[...], gate_ref[...])
    g = lax.dot_general(rows, gate, contract_minor,
                        preferred_element_type=jnp.float32)
    rows, up = _operands(rows_ref[...], up_ref[...])
    u = lax.dot_general(rows, up, contract_minor,
                        preferred_element_type=jnp.float32)
    h_ref[...] = (jax.nn.silu(g) * u).astype(h_ref.dtype)


def _down_kernel(group_ref, layer_ref, tiles_ref, token_ref, used_ref,
                 h_ref, down_ref, gate_ref, y_ref, o_ref):
    """One tile against one block of its expert's ``down``; the tile's rows
    in use, weighed, are added to their tokens' rows of ``y``'s block, which
    stays in the core's memory over all the tiles."""
    from jax.experimental import pallas as pl

    del group_ref, layer_ref
    t = pl.program_id(1)
    tm = h_ref.shape[0]

    @pl.when(t == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(t < tiles_ref[0])
    def _():
        h, down = _operands(h_ref[...], down_ref[...])
        o_ref[...] = gate_ref[...] * jnp.dot(
            h, down, preferred_element_type=jnp.float32)

        def add_row(r, carry):
            token = token_ref[t * tm + r]
            y_ref[pl.ds(token, 1), :] += o_ref[pl.ds(r, 1), :]
            return carry

        lax.fori_loop(0, used_ref[t], add_row, 0)


def gated_mlp(x, gate_up, down, lay: Layout, gates, *, layer=None):
    """Each token's sum over its held experts ``g`` of ``gates[n, g] *
    down_g(silu(gate_g(x_n)) * up_g(x_n))``: ``x`` ``(N, d)`` in the compute
    type and ``gates`` ``(N, held)`` float32 -> ``(N, d)`` float32, the
    assignments those of ``lay``.  ``gate_up`` ``(held, 2f, d)`` and
    ``down`` ``(held, f, d)``, or both with the model's layers leading and
    ``layer`` the index of this one."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if layer is None:
        gate_up, down, layer = gate_up[None], down[None], 0
    tm = lay.tm
    n, d = x.shape
    f = down.shape[-2]
    itemsize = jnp.dtype(gate_up.dtype).itemsize
    n_tiles = lay.n_tiles
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)

    tn = _block_columns(f, d * itemsize)
    by_expert = pl.BlockSpec(
        (None, 1, n), lambda t, j, group, layer: (group[t], 0, 0))
    h, row_gate = pl.pallas_call(
        _gate_up_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles[0], f // tn),
            in_specs=[
                pl.BlockSpec((n, d), lambda t, j, group, layer: (0, 0)),
                by_expert, by_expert,
                pl.BlockSpec((None, None, tn, d),
                             lambda t, j, group, layer:
                             (layer[0], group[t], j, 0)),
                pl.BlockSpec((None, None, tn, d),
                             lambda t, j, group, layer:
                             (layer[0], group[t], j + f // tn, 0)),
            ],
            out_specs=[
                pl.BlockSpec((tm, tn), lambda t, j, group, layer: (t, j)),
                pl.BlockSpec((tm, 1), lambda t, j, group, layer: (t, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((tm, d), x.dtype)],
        ),
        out_shape=[jax.ShapeDtypeStruct((lay.rows, f), x.dtype),
                   jax.ShapeDtypeStruct((lay.rows, 1), jnp.float32)],
        compiler_params=params,
        interpret=_fa._interpret(),
        name="expert_gate_up",
    )(lay.tile_group, layer, x, lay.dest.T[:, None, :], gates.T[:, None, :],
      gate_up, gate_up)

    tn = _block_columns(d, f * itemsize)

    def tile(t, tiles):
        # A turn past the tiles in use (the one turn of a call without an
        # assignment) stays on the last block fetched.
        return jnp.minimum(t, jnp.maximum(tiles[0] - 1, 0))

    return pl.pallas_call(
        _down_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            # The blocks of ``y`` outermost: one stays put while every tile
            # adds to it, and is written once.
            grid=(d // tn, jnp.maximum(n_tiles[0], 1)),
            in_specs=[
                pl.BlockSpec((tm, f), lambda j, t, group, layer, tiles, *_:
                             (tile(t, tiles), 0)),
                pl.BlockSpec((None, None, f, tn),
                             lambda j, t, group, layer, tiles, *_:
                             (layer[0], group[tile(t, tiles)], 0, j)),
                pl.BlockSpec((tm, 1), lambda j, t, group, layer, tiles, *_:
                             (tile(t, tiles), 0)),
            ],
            out_specs=pl.BlockSpec((n, tn), lambda j, t, *_: (0, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=params,
        interpret=_fa._interpret(),
        name="expert_down",
    )(lay.tile_group, layer, n_tiles, lay.row_token, lay.tile_used,
      h, down, row_gate)


def gated_mlp_reference(x, gate_up, down, lay: Layout, gates, *, layer=None):
    """``gated_mlp`` in plain ``jnp`` over the same layout: each tile with
    its expert's stack, gathered a tile (a toy's worth of memory, which is
    what runs off the TPU), and each token's results gathered, weighed and
    summed in the experts' order."""
    if layer is not None:
        gate_up, down = (lax.dynamic_index_in_dim(w, layer, keepdims=False)
                         for w in (gate_up, down))
    tiles = lay.tile_group.shape[0]
    rows = x[lay.row_token].reshape(tiles, lay.tm, -1)
    g, u = jnp.split(jnp.einsum(
        "tmd,tgd->tmg", *_operands(rows, gate_up[lay.tile_group]),
        preferred_element_type=jnp.float32), 2, axis=-1)
    out = jnp.einsum(
        "tmf,tfd->tmd", *_operands((jax.nn.silu(g) * u).astype(x.dtype),
                                   down[lay.tile_group]),
        preferred_element_type=jnp.float32).reshape(lay.rows, -1)
    # A row no assignment names holds whatever its tile's expert made of
    # it: chosen, not multiplied, away.
    return jnp.sum(jnp.where((lay.dest >= 0)[:, :, None],
                             gates[:, :, None] * out[lay.dest], 0.0), axis=1)
