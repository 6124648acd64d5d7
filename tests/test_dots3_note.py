"""The family whose window layers keep latent rows in a ring beside full
layers that read an indexer's selection (``models/dots3_note.py``) at a tiny
size on the CPU, against the benchmark's plain reference
(``benchmark/reference/dots3_note.py``: full forward pass, the whole ``I[t,
s]`` matrix, ``lax.top_k``, masks, float32).

What is held here: (a) the full forward gives the reference's logits; (b)
prefill in chunks and then decode through the three pools gives them too,
with contexts past the window, past ``index_topk`` and a prompt longer than
the ring; (c) each full layer reads the reference's selected sets and each
window layer exactly its window, whatever lies in the cells and blocks it
may not read; (d) the 32 shares of an expert layer add up to the uncut
layer; (e) the gate and the rescale are in force; (f) the pools' geometry,
and the scheduler's pool statistics, this family's and the other three's;
(g) the scheduler refuses what the family cannot take.

Tolerances: float32 on both sides, so 3e-5 on logits of magnitude ~1 is
the sums' order alone (bfloat16 in float32's place reads 1e-2 and more: the
bfloat16 case is given its own, wider one, as the other families' tests
are).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import dots3_note as ref
from benchmark.reference import precision
from distributed_tensorflow_tpu.models import PagedKVConfig
from distributed_tensorflow_tpu.models import decoder_parts as parts
from distributed_tensorflow_tpu.models import dots3_note as dots
from distributed_tensorflow_tpu.models.dots3_note import (
    Dots3Note, Dots3NoteConfig)
from distributed_tensorflow_tpu.obs.metrics import default_registry
from distributed_tensorflow_tpu.serve import ContinuousScheduler, ServeEngine
from tests.helpers import (
    expert_forms_on_record, pool_stat_keys_are, zero_cache)

EXACT = precision.Exact()
TOLERANCE = 3e-5
WINDOW, TOPK = 25, 24           # the tiny preset's


def tiny(**kw):
    kw.setdefault("dtype", jnp.float32)
    return Dots3NoteConfig.tiny(**kw)


# The one configuration every test of a whole model runs (a program and a
# reference are compiled a configuration): this chip holds experts 2-5 of 8.
SERVED = tiny(experts_held=4, first_expert=2)
CHUNK = 16


def reference_config(cfg):
    """The configuration file's keys the reference reads, from the
    program's configuration object."""
    keys = ("num_hidden_layers", "first_k_dense_replace",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_theta", "swa_num_attention_heads", "swa_q_lora_rank",
            "swa_kv_lora_rank", "swa_qk_nope_head_dim",
            "swa_qk_rope_head_dim", "swa_v_head_dim", "swa_rope_theta",
            "sliding_window_size", "apply_mla_qkv_lora_rescale",
            "attention_gate_type", "swa_attention_gate_type",
            "index_n_heads", "index_head_dim", "index_topk",
            "index_norm_eps", "rms_norm_eps", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor")
    return dict(
        {key: getattr(cfg, key) for key in keys},
        layer_types=list(cfg.layer_types), n_routed_experts=cfg.held,
        first_expert_held=cfg.first_expert,
        parameter_dtype=jnp.dtype(cfg.dtype).name)


@functools.cache
def drawn_params(cfg, seed=3):
    """Random parameters (norm scales round 1, offsets and a correction
    bias that move choices), in the type the module holds them in."""
    module = Dots3Note(cfg)
    abstract = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    keys = iter(jax.random.split(jax.random.key(seed), 400))

    def one(path, leaf):
        name = path[-1].key
        noise = jax.random.normal(next(keys), leaf.shape, jnp.float32)
        value = {"scale": 1.0 + 0.1 * noise, "bias": 0.05 * noise}.get(
            name, 0.05 * noise)
        return value.astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(one, abstract)


def tokens_of(cfg, shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, shape), jnp.int32)


REFERENCE_LENGTH = 112      # every row the reference sees, padded to this


@functools.cache
def _reference(cfg):
    def run(f32, tokens):
        masks = []
        logits = ref.logits(EXACT, reference_config(cfg), f32, tokens, masks)
        return logits, masks

    return jax.jit(run)


def reference_run(cfg, params, tokens):
    """The reference's logits and each layer's mask, of rows padded to
    ``REFERENCE_LENGTH`` (what follows a position moves nothing at it: the
    model is causal), so the reference compiles one program a
    configuration and row count: run op by op, or compiled a length, it is
    most of this file's time."""
    rows, t = tokens.shape
    f32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    padded = jnp.zeros((rows, REFERENCE_LENGTH), jnp.int32).at[:, :t].set(
        tokens)
    logits, masks = _reference(cfg)(f32, padded)
    return (np.asarray(logits)[:, :t],
            [np.asarray(m)[:, :t, :t] for m in masks])


@functools.cache
def forward(cfg):
    """The uncached forward pass, compiled (a shape a program)."""
    return jax.jit(lambda params, tokens: Dots3Note(cfg).apply(
        {"params": params}, tokens, mutable=["intermediates"]))


def selections_of(mutated, cfg):
    sown = mutated["intermediates"]
    return [sown[f"selection_{l}"][0] for l in range(cfg.num_hidden_layers)]


# -- the configuration ---------------------------------------------------------

def test_the_published_layer_list_and_the_two_kinds_sizes_are_the_defaults():
    cfg = Dots3NoteConfig.published()
    types = cfg.layer_types
    assert types[:6] == ("full_attention",) * 2 + ("sliding_attention",) * 3 + (
        "full_attention",)
    assert types[2:] == (("sliding_attention",) * 3 + ("full_attention",)) * 11
    assert (cfg.n_full_layers, cfg.n_window_layers) == (13, 33)
    assert cfg.layer_kinds[:3] == ("dense_full", "sparse_full",
                                   "sparse_sliding")
    full, slide = cfg.full, cfg.sliding
    assert (full.heads, full.latent_width, full.pool_width) == (128, 576, 640)
    assert (slide.heads, slide.latent_width, slide.pool_width) == (
        64, 1088, 1152)
    assert full.q_scale == slide.q_scale == slide.kv_scale == 5 ** 0.5
    assert full.kv_scale == 10 ** 0.5
    assert full.gated and slide.gated


def test_the_chips_share_preset_is_the_benchmarks_configuration():
    from benchmark.harness import program, spec

    share = Dots3NoteConfig.v5e256_share()
    assert share.layer_kinds == ("dense_full", "sparse_full") + (
        "sparse_sliding",) * 3
    assert (share.held, share.vocab_size, share.n_routed_experts) == (
        8, 19008, 256)
    assert share.layer_types == Dots3NoteConfig.published().layer_types[:5]
    cell = spec.load_cell("serve.dots3-note-prev.notes-mixed-saturated")
    assert program.program_config(cell.config) == share


def test_the_configuration_files_parameters_are_the_engines_leaves():
    """The ``parameters`` group of ``benchmark/configs/dots3-note-prev.json``
    against the module's own leaf shapes (shapes alone: nothing is
    drawn)."""
    import json
    import os
    import re

    from benchmark.harness import spec

    share = Dots3NoteConfig.v5e256_share()
    shapes = jax.eval_shape(lambda: Dots3Note(share).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    count = lambda tree: sum(int(np.prod(x.shape))
                             for x in jax.tree.leaves(tree))
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "dots3-note-prev.json")) as f:
        said = json.load(f)["parameters"]
    millions = lambda key: float(re.match(
        r"([\d,.]+)M", said[key]).group(1).replace(",", ""))
    full, slide = shapes["layer_1"], shapes["layer_2"]
    got = {
        "attention_mla_a_full_layer": count(full["attn"]),
        "indexer_a_full_layer": count(full["indexer"]),
        "attention_mla_a_window_layer": count(slide["attn"]),
        "dense_mlp": count(shapes["layer_0"]["mlp"]),
        "router": count(full["router"]),
        "shared_expert": count(full["shared"]),
        "one_routed_expert": count(full["experts"]) // 8,
        "layer_0_dense_full": count(shapes["layer_0"]),
        "layer_1_sparse_full": count(full),
        "layers_2_3_4_sparse_sliding": 3 * count(slide),
        "embedding_and_head_19008_rows": (count(shapes["embed"])
                                          + count(shapes["head"])),
        "sum": count(shapes),
    }
    for key, n in got.items():
        assert millions(key) == pytest.approx(n / 1e6, abs=0.06, rel=2e-4), key
    assert round(count(shapes) / 1e6) == 1822


@pytest.mark.parametrize("bad,match", [
    (dict(experts_held=0), "experts_held"),
    (dict(experts_held=4, first_expert=5), "first_expert"),
    (dict(layer_types=("full_attention",) * 4), "layer_types must name 5"),
    (dict(layer_types=("full", "full", "swa", "swa", "swa")), "layer_types"),
    (dict(attention_gate_type="elementwise"), "gate"),
    (dict(swa_qk_rope_head_dim=15), "even"),
    (dict(index_head_dim=8), "rotated"),
    (dict(index_topk=0), "index_topk"),
    (dict(sliding_window_size=0), "sliding_window_size"),
])
def test_config_refuses_what_is_no_such_model(bad, match):
    with pytest.raises(ValueError, match=match):
        tiny(**bad)


# -- (a) the full forward pass, (c) what each layer reads, (e) gate, rescale ---

def test_forward_matches_the_reference_and_reads_its_positions():
    """84 positions against a selection of 24 and a window of 25: most
    queries read a real selection and a real window.  (a) the logits; (c)
    every layer's mask is the reference's: each full layer selects for
    itself, a window layer reads t-24 .. t."""
    cfg, params = SERVED, drawn_params(SERVED)
    tokens = tokens_of(cfg, (2, 84))
    got, mutated = forward(cfg)(params, tokens)
    assert got.dtype == jnp.float32
    want, masks = reference_run(cfg, params, tokens)
    np.testing.assert_allclose(np.asarray(got), want, atol=TOLERANCE)
    mine = [np.asarray(m) for m in selections_of(mutated, cfg)]
    t = np.arange(84)
    for layer, (read, theirs) in enumerate(zip(mine, masks)):
        assert (read == theirs).all(), layer
        cap = TOPK if cfg.layer_types[layer] == dots.FULL else WINDOW
        assert (read.sum(-1) == np.minimum(t + 1, cap)).all()
    assert (mine[0] != mine[1]).any()
    window = (t[None, :] <= t[:, None]) & (t[:, None] - t[None, :] < WINDOW)
    for mask in mine[2:]:
        assert (mask == window[None]).all()


@pytest.mark.parametrize("changed,strip", [
    (dict(dtype=jnp.bfloat16), ()),
    (dict(apply_mla_qkv_lora_rescale=False), ()),
    (dict(attention_gate_type=None), ("layer_0", "layer_1")),
    (dict(swa_attention_gate_type=None), ("layer_2", "layer_3", "layer_4")),
], ids=["bfloat16", "no_rescale", "no_full_gate", "no_window_gate"])
def test_a_lower_precision_or_a_missing_mechanism_fails_the_tolerance(
        changed, strip):
    """(e) The same weights computed in bfloat16 in float32's place, or
    without the rescale, or with a kind's gate left out (a gate of ones),
    leave the reference by far more than the tolerance the sound program
    passes."""
    cfg, params = SERVED, drawn_params(SERVED)
    tokens = tokens_of(cfg, (2, 84))
    want, _ = reference_run(cfg, params, tokens)
    stripped = {name: ({**group, "attn": {k: v for k, v in
                                          group["attn"].items()
                                          if k != "gate"}}
                       if name in strip else group)
                for name, group in params.items()}
    got, _ = forward(dataclasses.replace(cfg, **changed))(stripped, tokens)
    assert np.abs(np.asarray(got) - want).max() > 10 * TOLERANCE


# -- (b) the three pools -------------------------------------------------------

def paged_for(slots, total, block, ring):
    """Tables whose rows hold the full layers' blocks (shuffled: block 0 is
    the trash block) and then the slot's ring."""
    per_slot = total // block
    free = iter(np.random.default_rng(5).permutation(
        np.arange(1, slots * per_slot + 1)))
    tables = np.zeros((slots, per_slot + ring), np.int32)
    for s in range(slots):
        tables[s, :per_slot] = [next(free) for _ in range(per_slot)]
        tables[s, per_slot:] = 1 + s * ring + np.arange(ring)
    paged = PagedKVConfig(
        block_size=block, num_blocks=slots * per_slot + 1,
        window_blocks=slots * ring + 1, window_ring=ring)
    return paged, jnp.asarray(tables)


class Cached:
    """``tokens`` (2, T) through the paged pools, into slots 2 and 0 of 3:
    a chunk a call, then a position a call over every slot."""

    @classmethod
    @functools.cache
    def served(cls, block, ring):
        """``SERVED``'s, its two programs compiled once a geometry: a test
        starts it over with ``fresh()``."""
        return cls(SERVED, drawn_params(SERVED), block=block, ring=ring)

    def fresh(self):
        self.cache = jax.tree.map(jnp.zeros_like, self.cache)
        return self

    def __init__(self, cfg, params, *, block, ring, total=96):
        self.cfg, self.module = cfg, Dots3Note(cfg)
        self.slots, self.total, self.block = 3, total, block
        self.paged, self.tables = paged_for(self.slots, total, block, ring)
        self.rows = jnp.asarray([2, 0], jnp.int32)
        every = jnp.arange(self.slots, dtype=jnp.int32)
        kw = dict(decode=True, paged=self.paged, block_tables=self.tables,
                  mutable=["cache", "intermediates"])
        self.cache = zero_cache(
            self.module, jnp.zeros((self.slots, 1), jnp.int32), decode=True,
            slot_ids=every, paged=self.paged, block_tables=self.tables)
        self.chunk_call = jax.jit(lambda cache, toks: self.module.apply(
            {"params": params, "cache": cache}, toks, slot_ids=self.rows,
            **kw))
        self.step_call = jax.jit(lambda cache, toks: self.module.apply(
            {"params": params, "cache": cache}, toks, slot_ids=every,
            live=jnp.asarray([True, False, True]), **kw))

    def chunk(self, tokens):
        got, mutated = self.chunk_call(self.cache, tokens)
        self.cache = mutated["cache"]
        return np.asarray(got), mutated

    def step(self, tokens, cache=None):
        step = jnp.zeros((self.slots, 1), jnp.int32).at[self.rows].set(tokens)
        got, mutated = self.step_call(
            self.cache if cache is None else cache, step)
        if cache is None:
            self.cache = mutated["cache"]
        return np.asarray(got)[np.asarray(self.rows)], mutated

    def run(self, tokens, *, prompt, chunk, selections=None):
        """-> the logits (2, T, V); ``selections`` receives, a call, each
        full layer's selection as a mask over positions (2, t, total)."""
        cfg, T = self.cfg, tokens.shape[1]
        fulls = [l for l, t in enumerate(cfg.layer_types) if t == dots.FULL]
        where = np.zeros((self.paged.num_blocks, self.block), np.int64)
        per_slot = self.total // self.block
        where[np.asarray(self.tables)[:, :per_slot]] = np.arange(
            self.total).reshape(-1, self.block)[None]
        out = []
        for off in range(0, prompt, chunk):
            got, mutated = self.chunk(tokens[:, off:off + chunk])
            out.append(got)
            if selections is not None:
                sown = selections_of(mutated, cfg)
                selections.append([np.asarray(sown[l]) for l in fulls])
        for t in range(prompt, T):
            got, mutated = self.step(tokens[:, t:t + 1])
            out.append(got)
            if selections is not None:
                sown, masks = selections_of(mutated, cfg), []
                for l in fulls:
                    blocks, offsets, valid = sown[l]
                    mask = np.zeros((self.slots, 1, self.total), bool)
                    at = where[np.asarray(blocks), np.asarray(offsets)]
                    for b in range(self.slots):
                        mask[b, 0, at[b][np.asarray(valid)[b]]] = True
                    masks.append(mask[np.asarray(self.rows)])
                selections.append(masks)
        return np.concatenate(out, axis=1)


@pytest.mark.parametrize("block,chunk,ring", [(8, 16, 6), (16, 32, 4)])
def test_chunked_prefill_then_decode_gives_the_reference_logits(
        block, chunk, ring, monkeypatch):
    """Logits, not tokens: a prompt of 64 into two slots at once, ``chunk``
    positions a call, then 20 positions one at a time through the three
    pools, against the reference's one full forward pass.  The ring holds
    48 positions (64 in the second case, exactly a chunk of 32, the window
    before it and a block to spare: the prompt fills it whole and the
    decode steps wrap it): the ring has wrapped before the last chunk; the
    context passes the window (25) and ``index_topk`` (24) in the second
    chunk; the context is walked 32 positions a step."""
    from distributed_tensorflow_tpu.models import paged_call

    monkeypatch.setattr(paged_call, "CONTEXT_CHUNK", 32)
    cfg, params = SERVED, drawn_params(SERVED)
    tokens = tokens_of(cfg, (2, 84))
    want, masks = reference_run(cfg, params, tokens)
    cached = Cached.served(block, ring).fresh()
    assert ring * block <= 64 < 84 <= cached.total
    sets = []
    got = cached.run(tokens, prompt=64, chunk=chunk, selections=sets)
    np.testing.assert_allclose(got, want, atol=TOLERANCE)
    # Every call's full layers read the reference's sets.
    at = 0
    for call in sets:
        t = call[0].shape[1]
        for layer, mask in zip((0, 1), call):
            theirs = masks[layer][:, at:at + t]
            assert (mask[..., :84] == theirs).all(), (at, layer)
            assert not mask[..., 84:].any()
        at += t
    assert at == 84
    pools = cached.cache
    assert pools["latent_pool"].shape == (
        2, cached.paged.num_blocks, block, 128)
    assert pools["index_pool"].shape == (
        2, cached.paged.num_blocks, block, 32)
    assert pools["window_pool"].shape == (3, 3 * ring + 1, block, 128)


def test_a_step_reads_its_window_and_its_own_blocks_and_nothing_else():
    """Every ring cell outside the two rows' windows, every ring another
    slot owns, every full-pool block the rows do not own and every cell of
    theirs past their length, overwritten with large values: the next
    step's logits do not move."""
    cfg = SERVED
    tokens = tokens_of(cfg, (2, 71), seed=3)
    block, ring = 8, 6
    cached = Cached.served(block, ring).fresh()
    cached.run(tokens[:, :70], prompt=64, chunk=16)
    t = 70                                  # the next step's position
    want, _ = cached.step(tokens[:, t:t + 1], cache=cached.cache)
    rows, tables = np.asarray(cached.rows), np.asarray(cached.tables)
    per_slot, cap = cached.total // block, ring * block
    poison = 1e3
    window_keep = np.zeros((3 * ring + 1, block), bool)
    for r in rows:
        for p in range(t - (WINDOW - 1), t + 1):
            c = p % cap
            window_keep[tables[r, per_slot + c // block], c % block] = True
    # The step itself writes position t: that cell may hold anything.
    full_keep = np.zeros((cached.paged.num_blocks, block), bool)
    for r in rows:
        for p in range(t + 1):
            full_keep[tables[r, p // block], p % block] = True
    assert window_keep.sum() == 2 * WINDOW and not window_keep.all()
    cache = dict(cached.cache)
    cache["window_pool"] = jnp.where(
        window_keep[None, :, :, None], cache["window_pool"], poison)
    for name in ("latent_pool", "index_pool"):
        cache[name] = jnp.where(
            full_keep[None, :, :, None], cache[name], poison)
    got, _ = cached.step(tokens[:, t:t + 1], cache=cache)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # And the window is all of it: one cell inside it changes the logits.
    p = t - (WINDOW - 1)
    c = p % cap
    cell = (tables[rows[0], per_slot + c // block], c % block)
    cache["window_pool"] = cache["window_pool"].at[:, cell[0], cell[1]].add(1.0)
    moved, _ = cached.step(tokens[:, t:t + 1], cache=cache)
    assert np.abs(moved[0] - want[0]).max() > 100 * TOLERANCE


def test_a_chunk_longer_than_the_ring_allows_is_refused_at_trace_time():
    cfg = SERVED
    cached = Cached(cfg, drawn_params(cfg), block=8, ring=4)  # 32 positions
    with pytest.raises(ValueError, match="still in the window ring"):
        cached.chunk(tokens_of(cfg, (2, 16)))           # 16 + 24 > 32
    module = Dots3Note(cfg)
    with pytest.raises(ValueError, match="window pool"):
        module.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32),
                    decode=True, slot_ids=jnp.zeros((1,), jnp.int32),
                    paged=PagedKVConfig(block_size=8, num_blocks=5),
                    block_tables=jnp.zeros((1, 4), jnp.int32))


# -- (d) the expert layer's share ----------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips' 2-of-8 shares of the tiny router's experts: their routed
    parts, and the shared expert counted once, are the reference's uncut
    layer."""
    whole = tiny()
    p = drawn_params(whole)["layer_1"]
    x = jnp.asarray(np.random.default_rng(7).normal(size=(24, 64)), jnp.float32)
    shared = parts.gated_mlp(p["shared"], x, jnp.float32)
    total, assigned = shared, 0
    for chip in range(4):
        cfg = dataclasses.replace(whole, experts_held=2, first_expert=2 * chip)
        mine = dict(p, experts=jax.tree.map(
            lambda w: w[2 * chip:2 * chip + 2], p["experts"]))
        y, row = parts.expert_layer(cfg, mine, x)
        total = total + (y - shared)
        assigned += int(row[:2].sum())
    assert assigned == 2 * 24      # every choice fell on exactly one chip
    want = ref.expert_ffn(EXACT, reference_config(whole), x, p)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=TOLERANCE)


# -- (f) the geometry ----------------------------------------------------------

def test_the_cache_geometry_counts_three_pools():
    cfg = tiny()
    paged, _ = paged_for(2, 32, 16, 2)
    g = dots.cache_geometry(cfg, paged)
    assert g["kind"] == "latent_indexed_window"
    assert (g["values_per_token_layer"], g["pool_width"]) == (48 + 16, 128)
    assert (g["window_values_per_token_layer"],
            g["window_pool_width"]) == (64 + 16, 128)
    assert (g["full_layers"], g["index_layers"], g["window_layers"]) == (
        2, 2, 3)
    assert (g["window_positions"], g["selected_positions"]) == (WINDOW, TOPK)
    assert g["bytes_per_token"] == 2 * (128 + 32) * 4 + 3 * 128 * 4
    assert g["bytes_per_token_past_window"] == 2 * (128 + 32) * 4
    assert g["full_pool_bytes"] == g["full_block_bytes"] * paged.num_blocks
    assert g["window_pool_bytes"] == (g["window_block_bytes"]
                                      * paged.window_blocks)
    assert g["pool_bytes"] == g["full_pool_bytes"] + g["window_pool_bytes"]
    assert (g["window_ring_blocks"], g["window_ring_positions"]) == (2, 32)
    # The cell's: 16 slots of 8,192 positions, a ring of 98 blocks.
    share = Dots3NoteConfig.v5e256_share()
    g = dots.cache_geometry(share, PagedKVConfig(
        block_size=16, num_blocks=16 * 512 + 1, window_blocks=16 * 98 + 1,
        window_ring=98))
    assert g["bytes_per_token_layer"] == 1280
    assert g["window_bytes_per_token_layer"] == 2304
    assert g["index_bytes_per_token_layer"] == 256
    assert (g["full_block_bytes"], g["index_block_bytes"],
            g["window_block_bytes"]) == (49152, 8192, 110592)
    assert g["bytes_per_token"] == 9984
    assert g["bytes_per_token_past_window"] == 3072
    assert g["window_ring_positions"] == 1568
    assert g["pool_bytes"] == (16 * 512 + 1) * 49152 + (16 * 98 + 1) * 110592


# -- through the engine and the scheduler --------------------------------------

@pytest.fixture(scope="module")
def engine():
    eng = ServeEngine("dots3_note", config=SERVED)
    eng.install_params(eng.shard_params(drawn_params(SERVED)))
    yield eng
    eng.close()


def scheduler(engine, **kw):
    args = dict(num_slots=4, max_total_len=128, cache_mode="paged",
                block_size=8, megastep=4, prefill_budget=CHUNK)
    args.update(kw)
    return ContinuousScheduler(engine, **args)


_REFERENCE_LOGITS = {}      # a sequence's, computed once whoever served it


def _gap_to_reference_best(engine, prompt, answer):
    """At every answered position, how far the served token's logit lies
    under the best logit of the reference's full forward pass."""
    seq = np.concatenate([prompt, answer])[None, :-1]
    key = seq.tobytes()
    if key not in _REFERENCE_LOGITS:
        _REFERENCE_LOGITS[key] = reference_run(     # two rows: one program
            SERVED, engine.params, jnp.asarray(np.repeat(seq, 2, 0)))[0]
    at = _REFERENCE_LOGITS[key][0, len(prompt) - 1:]
    return at.max(-1) - at[np.arange(len(answer)), answer]


@pytest.mark.parametrize("megastep,async_decode", [(4, True)])
def test_scheduler_serves_the_reference_best_tokens(engine, megastep,
                                                    async_decode):
    """Greedy answers through the three pools, rows longer than the window,
    the selection and the ring, launched as the cell launches them (four
    fused steps, dispatched ahead): every token the reference's own first
    choice at its position."""
    rng = np.random.default_rng(0)
    requests = [(rng.integers(0, SERVED.vocab_size, n, dtype=np.int32), new)
                for n, new in ((48, 40), (80, 30), (16, 7))]
    with scheduler(engine, megastep=megastep,
                   async_decode=async_decode) as sched:
        ring = sched.paged.window_ring
        assert ring == -(-(WINDOW + CHUNK + megastep) // 8) + 1
        assert ring * 8 < 80
        futures = [sched.submit(p, max_new_tokens=n) for p, n in requests]
        answers = [np.asarray(f.result(timeout=600)) for f in futures]
        stats = sched.stats()
    assert stats["moe_layer_steps"] > 0 and stats["moe_experts_held"] == 4
    assert stats["window_blocks_recycled"] > 0
    assert (stats["decode_live_positions_window"]
            < stats["decode_live_positions"])
    assert (stats["decode_selected_positions"]
            < stats["decode_live_positions"])
    for (prompt, new), answer in zip(requests, answers):
        assert len(answer) == new
        assert _gap_to_reference_best(engine, prompt, answer).max() <= 1e-4
    paths = engine.attention_paths()
    assert set(paths["slot_prefill"]) == {dots.MASKED, dots.WINDOW_CHUNK}
    assert set(paths["slot_megastep"]) == {dots.SELECTED, dots.WINDOW_STEP}
    launches = engine.decode_attention_launches()
    assert launches[dots.SELECTED] == launches[dots.WINDOW_STEP] > 0


def test_stats_name_the_form_each_programs_expert_layers_took(engine):
    prompt = np.random.default_rng(2).integers(
        0, SERVED.vocab_size, 2 * CHUNK, dtype=np.int32)
    with scheduler(engine) as sched:
        sched.submit(prompt, max_new_tokens=6).result(timeout=300)
        expert_forms_on_record(sched, experts=SERVED.n_routed_experts,
                               chunk=CHUNK)


def test_the_pool_statistics_compose(engine):
    """One long row: latent and index blocks grow with it, one of each a
    table block; window blocks stop at the ring and recycle; the bytes are
    each pool's own; retirement returns them all."""
    gauge = default_registry().gauge(
        "dtt_serve_kv_blocks_held", labelnames=("kind",))
    total = 3 * (WINDOW + CHUNK)
    prompt = np.random.default_rng(1).integers(
        0, SERVED.vocab_size, 2 * CHUNK, dtype=np.int32)
    seen = []
    with scheduler(engine, num_slots=2, start=False) as sched:
        ring, g = sched.paged.window_ring, sched._kv_geometry
        future = sched.submit(
            prompt, max_new_tokens=total - len(prompt),
            on_token=lambda toks: seen.append((
                sched.stats(), {kind: gauge.labels(kind=kind).value
                                for kind in ("latent", "index", "window")})))
        sched._thread.start()
        assert len(future.result(timeout=600)) == total - len(prompt)
        after = sched.stats()
    assert g["full_block_bytes"] == 2 * 8 * (128 + 32) * 4
    assert g["window_block_bytes"] == 3 * 8 * 128 * 4
    live = [(s, k) for s, k in seen if s["kv_blocks_held_latent"] > 0]
    assert live
    for stats, kinds in live:
        held = stats["kv_blocks_held_latent"]
        window = stats["kv_blocks_held_window"]
        assert stats["kv_blocks_held_index"] == held
        assert window == min(held, ring)
        assert stats["kv_bytes_held"] == (held * g["full_block_bytes"]
                                          + window * g["window_block_bytes"])
        assert stats["kv_bytes_held_index"] == held * g["index_block_bytes"]
        assert stats["kv_bytes_held_uniform"] == held * (
            g["full_block_bytes"] + g["window_block_bytes"])
        assert stats["window_ring_blocks"] == ring
    assert max(s["kv_blocks_held_latent"] for s, _ in live) > 2 * ring
    assert any(k == {"latent": s["kv_blocks_held_latent"],
                     "index": s["kv_blocks_held_index"],
                     "window": s["kv_blocks_held_window"]} for s, k in live)
    assert after["window_blocks_recycled"] == -(-(total - 1) // 8) - ring
    for kind in ("latent", "index", "window"):
        assert after[f"kv_blocks_held_{kind}"] == 0
        assert gauge.labels(kind=kind).value == 0
    assert after["kv_bytes_held"] == 0
    assert after["decode_selected_positions"] == TOPK
    assert after["decode_live_positions_window"] == WINDOW


def test_stats_hold_the_rings_and_the_index_keys_keys_together(engine):
    """The other three families' key sets are held in their own files
    (``tests.helpers.pool_stat_keys_are``)."""
    pool_stat_keys_are(engine, "ring", "index")


def test_the_prefill_chunk_span_carries_its_context(engine):
    from distributed_tensorflow_tpu.obs.trace import default_tracer

    tracer = default_tracer()
    prompt = np.random.default_rng(3).integers(
        0, SERVED.vocab_size, 48, dtype=np.int32)
    was = tracer.enabled
    tracer.enable()
    try:
        with scheduler(engine, num_slots=2) as sched:
            sched.submit(prompt, max_new_tokens=2).result(timeout=300)
        chunks = [args for _, _, _, _, args in tracer.spans(
            name="dtt/serve/prefill_chunk")]
    finally:
        tracer.enabled = was
    assert [(c["offset"], c["chunk_tokens"], c["context_tokens"])
            for c in chunks[-3:]] == [(0, 16, 16), (16, 16, 32), (32, 16, 48)]


# -- (g) the refusals ----------------------------------------------------------

REFUSED = {
    "dense_cache": dict(cache_mode="dense"),
    "kv_dtype": dict(kv_dtype="int8"),
    "per_shard_kv": dict(per_shard_kv=True),
    "slo_scheduling": dict(slo_scheduling=True),
    "spec_k": dict(spec_k=2),
    "prefix_cache": dict(prefix_cache=True),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_scheduler_refuses_what_the_three_pools_cannot_serve(engine, feature):
    kwargs = dict(num_slots=2, max_total_len=64, cache_mode="paged",
                  block_size=16, start=False)
    kwargs.update(REFUSED[feature])
    reason = dots.SERVE_REFUSALS[feature]
    with pytest.raises(ValueError) as refused:
        ContinuousScheduler(engine, **kwargs)
    assert feature in str(refused.value) and reason in str(refused.value)


def test_a_tensor_mesh_is_refused_with_its_reason(mesh_2d):
    with pytest.raises(ValueError, match="tensor"):
        ServeEngine("dots3_note", mesh=mesh_2d, config=SERVED)


def test_the_module_has_no_dense_row_cache():
    module = Dots3Note(tiny())
    with pytest.raises(ValueError, match="paged only"):
        module.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32),
                    decode=True)
    paged = PagedKVConfig(block_size=16, num_blocks=5, kv_dtype="int8")
    with pytest.raises(ValueError, match="kv_dtype"):
        module.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32),
                    decode=True, slot_ids=jnp.zeros((1,), jnp.int32),
                    paged=paged, block_tables=jnp.zeros((1, 4), jnp.int32))


def test_engine_reports_the_cache_geometry(engine):
    paged = PagedKVConfig(block_size=16, num_blocks=9, window_blocks=7,
                          window_ring=3)
    geometry = engine.cache_geometry(paged)
    assert geometry == dots.cache_geometry(SERVED, paged)
    assert geometry["kind"] == "latent_indexed_window"


# -- through serve.py's driver -------------------------------------------------

def test_the_serve_driver_takes_the_family():
    from distributed_tensorflow_tpu.serve.driver import ServeArgs, run_serve

    out = run_serve(ServeArgs(
        model="dots3_note", continuous=True, cache_mode="paged",
        num_slots=4, steps=6, megastep=4, async_decode=True,
        prefill_budget=64))
    assert out["model"] == "dots3_note" and out["preset"] == "tiny"
    assert out["completed"] == 6 and out["compile_post_warmup"] == 0
    assert out["cache_mode"] == "paged" and out["tokens_generated"] > 0


def test_the_serve_driver_refuses_the_fixed_batch_path_with_the_reason():
    from distributed_tensorflow_tpu.serve.driver import ServeArgs, run_serve

    with pytest.raises(ValueError, match="--continuous --cache_mode=paged"):
        run_serve(ServeArgs(model="dots3_note", steps=2))
