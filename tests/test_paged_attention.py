"""The block-table decode attention kernel (``ops/paged_attention.py``) in
the Pallas interpreter, held to the gather path of
``models/gpt2.py: _paged_cached_attention`` within bf16 rounding, and its
grouped form (one pool of K and V side by side, a first position, a table
that is a ring) to the gather path of ``models/mellum.py``.

The interpreter shows the kernel's logic: which blocks a row fetches, the
online softmax over chunks, the masked tail, rows that fetch nothing.  What
the chip's compiler makes of it is ``tests/test_chip_compile.py``'s, and the
numbers at the cell's size are the benchmark's (``served_logit_gap_max``).
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models import decoder_parts as parts
from distributed_tensorflow_tpu.models import mellum
from distributed_tensorflow_tpu.models.gpt2 import (
    GPT2, GPT2Config, PagedKVConfig)

pa = importlib.import_module("distributed_tensorflow_tpu.ops.paged_attention")

HEADS, HEAD_DIM, BLOCK, MAX_BLOCKS, LAYERS = 4, 32, 16, 64, 3
WIDTH = HEADS * HEAD_DIM
TRASH = 0


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")


def gather_path(q, k_pool, v_pool, tables, lengths, layer=None):
    """``_paged_cached_attention`` after its scatter, as the file has it:
    the whole table row gathered, scores rounded to the compute type, f32
    softmax, probabilities rounded, the causal mask by the row's length."""
    if layer is not None:
        k_pool, v_pool = k_pool[layer], v_pool[layer]
    B, _, h, head_dim = q.shape
    S = tables.shape[1] * k_pool.shape[1]
    gk = k_pool[tables].reshape(B, S, h, head_dim)
    gv = v_pool[tables].reshape(B, S, h, head_dim)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, gk) / np.sqrt(head_dim)
    mask = jnp.arange(S)[None, None, :] < lengths[:, None, None]
    scores = jnp.where(mask[:, None], scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), gv)


def pools(rng, num_blocks, layers=LAYERS, trash=None):
    """Two random pools; ``trash`` fills block 0 of both with that value."""
    shape = (layers, num_blocks, BLOCK, WIDTH)
    k, v = (rng.normal(size=shape).astype(np.float32) for _ in range(2))
    if trash is not None:
        k[:, TRASH], v[:, TRASH] = trash, -trash
    return jnp.asarray(k, jnp.bfloat16), jnp.asarray(v, jnp.bfloat16)


def tables_for(rng, lengths, num_blocks, fill=TRASH):
    """Each row's blocks drawn without replacement from 1..num_blocks-1; the
    entries a row does not use point at ``fill``."""
    tables = np.full((len(lengths), MAX_BLOCKS), fill, np.int32)
    free = list(rng.permutation(np.arange(1, num_blocks)))
    for row, n in enumerate(lengths):
        for b in range(-(-int(n) // BLOCK)):
            tables[row, b] = free.pop()
    return tables


def queries(rng, rows):
    return jnp.asarray(rng.normal(size=(rows, 1, HEADS, HEAD_DIM)),
                       jnp.bfloat16)


def assert_within_bf16_rounding(got, want, rows):
    """Outputs are of order 1 (averages of unit normals), so one bf16 step
    is 2**-8 at most; the gather path itself sits two or three steps from
    exact arithmetic (its scores and probabilities are rounded).  In
    float32 only the order of the sums differs: one position too many or
    too few of a window of 40 moves an output by a fortieth."""
    atol = 1e-5 if got.dtype == jnp.float32 else 3 * 2.0 ** -8
    got = np.asarray(got, np.float32)[rows]
    want = np.asarray(want, np.float32)[rows]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0.0, atol=atol)


EDGE_LENGTHS = [1, 15, 16, 17, 250, 1023, 1024]


def case_edge_lengths(rng):
    """Every boundary of a block and of a chunk in one batch."""
    lengths = np.array(EDGE_LENGTHS)
    return dict(lengths=lengths, tables=tables_for(rng, lengths, 300),
                pools=pools(rng, 300), layer=1)


def case_empty_and_dead_rows(rng):
    """Rows of length 0 (what the model makes of a dead or inactive row)
    fetch nothing, return zeros, and leave the rows after them right:
    first, in the middle, two in a row, and last."""
    lengths = np.array([0, 40, 0, 0, 130, 17, 0])
    return dict(lengths=lengths, tables=tables_for(rng, lengths, 40),
                pools=pools(rng, 40), layer=0, zero_rows=lengths == 0)


def case_trash_block_garbage(rng):
    """Unused table entries point at the trash block, which holds large
    finite garbage (as do the positions past a row's length inside its
    last block): probability exactly 0 times garbage must stay 0."""
    lengths = np.array([5, 16, 100, 129, 1000])
    k_pool, v_pool = pools(rng, 120, trash=3.0e38)
    tables = tables_for(rng, lengths, 120)
    flat_k = np.array(k_pool.astype(jnp.float32))
    flat_v = np.array(v_pool.astype(jnp.float32))
    for row, n in enumerate(lengths):
        if n % BLOCK:
            last = tables[row, n // BLOCK]
            flat_k[:, last, n % BLOCK:] = -3.0e38
            flat_v[:, last, n % BLOCK:] = 3.0e38
    return dict(lengths=lengths, tables=tables, layer=2,
                pools=(jnp.asarray(flat_k, jnp.bfloat16),
                       jnp.asarray(flat_v, jnp.bfloat16)))


def case_shared_prefix_blocks(rng):
    """Two rows behind a prefix-cache hit map the same physical blocks for
    their first 48 positions and their own after."""
    lengths = np.array([70, 200, 33])
    tables = tables_for(rng, lengths, 60)
    tables[1, :3] = tables[0, :3]
    return dict(lengths=lengths, tables=tables, pools=pools(rng, 60),
                layer=1)


def case_unrolled_layout(rng):
    """``layer=None``: the pool is one layer's own ``(blocks, 16, width)``."""
    lengths = np.array([1, 31, 128, 129, 640])
    k_pool, v_pool = pools(rng, 80, layers=1)
    return dict(lengths=lengths, tables=tables_for(rng, lengths, 80),
                pools=(k_pool[0], v_pool[0]), layer=None)


def gqa_gather_path(q, pool, tables, lengths, window, layer):
    """``models/mellum.py``'s gather path after its scatter, with its own
    mask and product: the whole table row (a window layer's whole ring,
    each cell's position worked out from the row's length) gathered, K and
    V split out of it, attended under the mask."""
    B, _, kv_heads, groups, head_dim = q.shape
    cfg = mellum.MellumConfig.tiny(
        num_attention_heads=kv_heads * groups, num_key_value_heads=kv_heads,
        head_dim=head_dim, dtype=q.dtype)
    rows = pool[layer, tables].reshape(B, -1, pool.shape[-1])
    cells = rows.shape[1]
    shape = (B, cells, kv_heads, head_dim)
    k = rows[..., :kv_heads * head_dim].reshape(shape)
    v = rows[..., kv_heads * head_dim:].reshape(shape)
    last = lengths[:, None] - 1
    held = jnp.broadcast_to(jnp.arange(cells)[None], (B, cells))
    if window is not None:
        held = last - (last - held) % cells
    mask = parts.attention_mask(last, held, window)
    return parts.gqa_attend(cfg, q, k, v, mask).reshape(q.shape)


def grouped_case(name, lengths, *, kv_heads, groups, head_dim, window=None,
                 ring=None, dtype=jnp.float32):
    """A case of the grouped form: ``lengths`` rows over one pool of K and
    V side by side.  ``window`` None is a full layer (the table as long as
    the longest row); else a window layer whose table is a ring of ``ring``
    blocks, every cell written (a row shorter than the ring reads only its
    own positions out of it)."""
    def make(rng):
        n = np.array(lengths)
        entries = ring or -(-int(n.max()) // BLOCK)
        num_blocks = len(n) * entries + 1
        tables = 1 + rng.permutation(len(n) * entries).reshape(
            len(n), entries).astype(np.int32)
        pool = jnp.asarray(rng.normal(size=(
            2, num_blocks, BLOCK, 2 * kv_heads * head_dim)), dtype)
        return dict(lengths=n, tables=tables, pools=(pool,), layer=1,
                    zero_rows=n == 0, window=window,
                    grouped=(kv_heads, groups, head_dim))
    make.__name__ = "case_" + name
    return make


# A window of 40 over blocks of 16 in a ring of 5 (80 cells): rows shorter
# than the window, exactly it and one more; the first position in the middle
# of a block (all but 168 and the chunk's own), in the middle of a chunk
# (1000: 960 of 512..1023), on a chunk's first position, and in the chunk
# before the last position's; past the ring's capacity once (100) and
# several times; dead rows between live ones.
WINDOW_LENGTHS = [7, 39, 40, 41, 0, 100, 168, pa.GROUPED_CHUNK + 40, 0, 0,
                  pa.GROUPED_CHUNK + 18, 1000, 1]
GROUPED_CASES = [
    grouped_case("gqa_full_g2_d16", [0, 1, 16, 17, 100, 129, 0, 300, 256],
                 kv_heads=2, groups=2, head_dim=16),
    grouped_case("gqa_full_g8_d128", [250, 0, 1023, 1],
                 kv_heads=4, groups=8, head_dim=128, dtype=jnp.bfloat16),
    grouped_case("gqa_window_g1_d128", WINDOW_LENGTHS, kv_heads=2, groups=1,
                 head_dim=128, window=40, ring=5),
    grouped_case("gqa_window_g2_d16", WINDOW_LENGTHS, kv_heads=2, groups=2,
                 head_dim=16, window=40, ring=5),
    grouped_case("gqa_window_g8_d16", WINDOW_LENGTHS, kv_heads=2, groups=8,
                 head_dim=16, window=40, ring=5, dtype=jnp.bfloat16),
    # The published heads, window and ring: a row inside the window, at it,
    # one past it, at the ring's capacity, one past it, wrapped twice over.
    grouped_case("gqa_window_published", [1000, 1024, 1025, 0, 1568, 1569,
                                          4000],
                 kv_heads=4, groups=8, head_dim=128, window=1024, ring=98,
                 dtype=jnp.bfloat16),
]

CASES = [case_edge_lengths, case_empty_and_dead_rows,
         case_trash_block_garbage, case_shared_prefix_blocks,
         case_unrolled_layout] + GROUPED_CASES


@pytest.mark.parametrize("make", CASES, ids=lambda f: f.__name__[5:])
def test_kernel_matches_the_gather_path(interpreter, make):
    rng = np.random.default_rng(29)
    case = make(rng)
    lengths = jnp.asarray(case["lengths"], jnp.int32)
    tables = jnp.asarray(case["tables"])
    layer = case["layer"]
    if "grouped" in case:
        (pool,), window = case["pools"], case["window"]
        q = jnp.asarray(rng.normal(size=(len(lengths), 1) + case["grouped"]),
                        pool.dtype)
        firsts = (None if window is None
                  else jnp.maximum(lengths - window, 0))
        got = jax.jit(lambda *a: pa.paged_decode_attention(
            a[0], a[1], None, *a[2:], layer=jnp.int32(layer),
            firsts=firsts))(q, pool, tables, lengths)
        want = gqa_gather_path(q, pool, tables, lengths, window, layer)
    else:
        k_pool, v_pool = case["pools"]
        q = queries(rng, len(lengths))
        got = jax.jit(lambda *a: pa.paged_decode_attention(
            *a, layer=None if layer is None else jnp.int32(layer)))(
                q, k_pool, v_pool, tables, lengths)
        want = gather_path(q, k_pool, v_pool, tables, lengths, layer)
    assert got.shape == q.shape and got.dtype == q.dtype
    zero_rows = case.get("zero_rows", np.zeros(len(lengths), bool))
    assert_within_bf16_rounding(got, want, ~zero_rows)
    assert not np.asarray(got, np.float32)[zero_rows].any()


# (id, query length, kv_dtype, mesh axes or None, interpreter on, platform)
SELECTION = [
    ("decode-plain-pool-tpu", 1, None, None, False, "tpu", pa.KERNEL),
    ("decode-plain-pool-interpreter", 1, None, None, True, "cpu", pa.KERNEL),
    ("decode-one-device-mesh", 1, None, 1, True, "cpu", pa.KERNEL),
    ("decode-cpu", 1, None, None, False, "cpu", pa.GATHER),
    ("prefill", 8, None, None, True, "cpu", pa.GATHER),
    ("verify-k-plus-one", 3, None, None, True, "cpu", pa.GATHER),
    ("decode-int8-pool", 1, "int8", None, True, "cpu", pa.GATHER),
    ("decode-cast-pool", 1, "float32", None, True, "cpu", pa.GATHER),
    ("decode-on-a-mesh", 1, None, 2, True, "cpu", pa.GATHER),
]


# The grouped-query family's own call: the same fields, then the heads
# (query heads, K/V heads, head size).  Its pools are stored in the compute
# type or refused, so there is no ``kv_dtype`` to choose by.
GQA_KERNEL = {parts.KERNEL_WINDOW, parts.KERNEL_FULL}
GQA_GATHER = {parts.GATHER_WINDOW, parts.GATHER_FULL}
GQA_SELECTION = [
    ("gqa-decode-tpu", 1, None, None, False, "tpu", GQA_KERNEL, (16, 2, 64)),
    ("gqa-decode-interpreter", 1, None, None, True, "cpu", GQA_KERNEL,
     (4, 2, 16)),
    ("gqa-decode-one-device-mesh", 1, None, 1, True, "cpu", GQA_KERNEL,
     (4, 2, 16)),
    ("gqa-decode-cpu", 1, None, None, False, "cpu", GQA_GATHER, (16, 2, 64)),
    ("gqa-prefill-chunk", 8, None, None, True, "cpu", GQA_GATHER, (4, 2, 16)),
    ("gqa-decode-on-a-mesh", 1, None, 2, True, "cpu", GQA_GATHER, (4, 2, 16)),
    # On the chip a K/V half of a pool row must be whole lane tiles, and a
    # group of query heads whole sublane tiles.
    ("gqa-decode-tpu-half-a-lane-tile", 1, None, None, False, "tpu",
     GQA_GATHER, (16, 2, 32)),
    ("gqa-decode-tpu-group-of-two", 1, None, None, False, "tpu", GQA_GATHER,
     (4, 2, 64)),
]


def gqa_paths(mesh, query_len, heads):
    """The paths ``Mellum``'s decode call of ``query_len`` positions a row
    takes, traced from shapes."""
    slots, total = 4, 64
    n_heads, kv_heads, head_dim = heads
    cfg = mellum.MellumConfig.tiny(
        num_attention_heads=n_heads, num_key_value_heads=kv_heads,
        head_dim=head_dim)
    model = mellum.Mellum(cfg, mesh=mesh)
    paged = PagedKVConfig(block_size=16, num_blocks=slots * 4 + 1,
                          window_blocks=slots * 4 + 1, window_ring=4)
    call = dict(decode=True, slot_ids=jnp.arange(slots, dtype=jnp.int32),
                paged=paged, block_tables=jnp.zeros((slots, 8), jnp.int32))
    variables = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((slots, total), jnp.int32), **call))
    with pa.record_paths() as paths:
        jax.eval_shape(
            lambda v, t: model.apply(v, t, mutable=["cache"], **call),
            variables, jax.ShapeDtypeStruct((slots, query_len), jnp.int32))
    return set(paths)


@pytest.mark.parametrize(
    "query_len,kv_dtype,mesh_devices,interpret,platform,expected,gqa_heads",
    [c[1:] + (None,) for c in SELECTION] + [c[1:] for c in GQA_SELECTION],
    ids=[c[0] for c in SELECTION + GQA_SELECTION])
def test_the_path_is_chosen_by_what_the_call_can_observe(
        monkeypatch, query_len, kv_dtype, mesh_devices, interpret, platform,
        expected, gqa_heads):
    """The model's own call, traced from shapes: which implementation
    ``_paged_cached_attention`` (or the grouped-query family's attention)
    takes (the CPU cannot lower the kernel for a TPU, so nothing is
    compiled)."""
    fa = importlib.import_module(
        "distributed_tensorflow_tpu.ops.flash_attention")
    monkeypatch.setattr(fa, "_platform", lambda: platform)
    if interpret:
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("DTT_PALLAS_INTERPRET", raising=False)
    mesh = None
    if mesh_devices is not None:
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:mesh_devices]), ("tensor",))
    if gqa_heads is not None:
        assert gqa_paths(mesh, query_len, gqa_heads) == expected
        return
    slots, total = 4, 32
    cfg = dataclasses.replace(GPT2Config.tiny(), d_model=256)  # whole lanes
    model = GPT2(cfg, mesh=mesh)
    paged = PagedKVConfig(block_size=16, num_blocks=slots * 2 + 1,
                          kv_dtype=kv_dtype)
    tables = jnp.zeros((slots, total // 16), jnp.int32)
    slot_ids = jnp.arange(slots, dtype=jnp.int32)
    variables = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((slots, total), jnp.int32),
        decode=True, slot_ids=slot_ids, paged=paged, block_tables=tables))

    def step(variables, tokens):
        return model.apply(variables, tokens, decode=True, slot_ids=slot_ids,
                           paged=paged, block_tables=tables,
                           mutable=["cache"])

    with pa.record_paths() as paths:
        jax.eval_shape(step, variables,
                       jax.ShapeDtypeStruct((slots, query_len), jnp.int32))
    assert set(paths) == {expected}


@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["scan", "unrolled"])
def test_model_decode_steps_match_the_gather_path(monkeypatch, scan_layers):
    """The model end to end, both layouts: a prefill (gather path on either
    side) and three decode steps of rows at different depths, one of them
    not live.  Logits under the interpreter's kernel agree with the gather
    path's within bf16 rounding through two layers, and a row that is not
    live leaves the others' untouched."""
    slots, total, block = 4, 64, 16
    cfg = GPT2Config.tiny(scan_layers=scan_layers)
    model = GPT2(cfg)
    paged = PagedKVConfig(block_size=block, num_blocks=slots * 4 + 1)
    tables = jnp.asarray(
        1 + np.random.default_rng(3).permutation(slots * 4).reshape(slots, 4),
        jnp.int32)
    slot_ids = jnp.arange(slots, dtype=jnp.int32)
    rng = np.random.default_rng(5)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (slots, 20)))
    variables = jax.jit(lambda: model.init(
        jax.random.key(0), jnp.zeros((slots, total), jnp.int32), decode=True,
        slot_ids=slot_ids, paged=paged, block_tables=tables))()
    params = variables["params"]
    empty = jax.tree.map(jnp.zeros_like, variables["cache"])
    steps = [jnp.asarray(rng.integers(0, cfg.vocab_size, (slots, 1)))
             for _ in range(3)]
    live = jnp.asarray([True, True, False, True])

    def run():
        apply = jax.jit(
            lambda cache, tokens, live: model.apply(
                {"params": params, "cache": cache}, tokens, decode=True,
                slot_ids=slot_ids, paged=paged, block_tables=tables,
                live=live, mutable=["cache"]))
        _, mutated = apply(empty, prompt, None)
        out = []
        for tokens in steps:
            logits, mutated = apply(mutated["cache"], tokens, live)
            out.append(np.asarray(logits, np.float32))
        return out, mutated["cache"]

    monkeypatch.delenv("DTT_PALLAS_INTERPRET", raising=False)
    with pa.record_paths() as paths:
        want, want_cache = run()
    assert set(paths) == {pa.GATHER}
    monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
    with pa.record_paths() as paths:
        got, got_cache = run()
    assert set(paths) == {pa.GATHER, pa.KERNEL}   # prefill, then decode
    rows = np.asarray(live)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g[rows], w[rows], rtol=0.0, atol=0.05)
    # Which rows advanced does not depend on the attention path.
    for name, leaf in _flat(got_cache).items():
        if name.endswith(("cache_index", "position")):
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(_flat(want_cache)[name]))


def _flat(tree):
    return {"/".join(str(getattr(p, "key", p)) for p in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def test_the_engine_records_the_path_and_counts_decode_launches(interpreter):
    """Through the scheduler on one device: the decode program is traced
    with the kernel and every prefill with the gather path, on record per
    program kind; the launch counter and ``stats()`` give the kernel's
    share of decode launches; every request completes."""
    from distributed_tensorflow_tpu import cluster as cluster_lib
    from distributed_tensorflow_tpu.serve import (
        ContinuousScheduler, ServeEngine)

    mesh = cluster_lib.build_mesh(cluster_lib.MeshConfig(),
                                  devices=jax.devices()[:1])
    rng = np.random.default_rng(1)
    with ServeEngine("gpt2", mesh=mesh, preset="tiny") as engine:
        before = engine.decode_attention_launches()
        vocab = engine.module.cfg.vocab_size
        requests = [(rng.integers(0, vocab, size=(n,), dtype=np.int32), m)
                    for n, m in ((4, 5), (9, 3), (20, 6), (4, 2), (9, 7))]
        with ContinuousScheduler(engine, num_slots=4, max_total_len=32,
                                 cache_mode="paged", block_size=16,
                                 megastep=2) as sched:
            futures = [sched.submit(p, max_new_tokens=m)
                       for p, m in requests]
            outs = [f.result(timeout=600) for f in futures]
            stats = sched.stats()
        after = engine.decode_attention_launches()
        paths = engine.attention_paths()
    for (_, horizon), out in zip(requests, outs):
        assert out.shape == (horizon,)
    assert set(paths["slot_prefill"]) == {pa.GATHER}
    assert set(paths["slot_megastep"]) == {pa.KERNEL}
    assert after[pa.KERNEL] > before[pa.KERNEL]
    assert after[pa.GATHER] == before[pa.GATHER]
    # The counter is the process's: the share is 1 only where no launch of
    # another path (the gather's, another family's latent attention) ran
    # in this process before.
    if sum(before.values()) == before[pa.KERNEL]:
        assert stats["decode_attention_kernel_share"] == 1.0
