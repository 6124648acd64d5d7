"""The grouped-query, window-and-full, sparse-expert decoder family
(``models/mellum.py``) at a tiny size on the CPU, against the benchmark's
plain reference (``benchmark/reference/mellum.py``: full forward pass, no
cache, float32).

What is held here: the full forward pass and, through both paged pools,
prefill in chunks and then decode give the reference's logits past the
window and past the ring's wrap; the scheduler serves the reference's own
best tokens, holds a ring's blocks and no more however long the row, and
gives both kinds back on retirement and on cancel; the YaRN table and the
banded mask match their closed forms; the four shares of the expert layer
add up to the uncut layer; every scheduler feature the family cannot serve
is refused with its reason; a float8 product fails the bfloat16 tolerance.
"""

import dataclasses
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mellum as ref
from benchmark.reference import precision
from distributed_tensorflow_tpu.models import PagedKVConfig, get_workload
from distributed_tensorflow_tpu.models import decoder_parts as parts
from distributed_tensorflow_tpu.models import mellum
from distributed_tensorflow_tpu.models.decoder_parts import route
from distributed_tensorflow_tpu.models.mellum import Mellum, MellumConfig
from distributed_tensorflow_tpu.obs.metrics import default_registry
from distributed_tensorflow_tpu.ops import paged_attention
from distributed_tensorflow_tpu.serve import ContinuousScheduler, ServeEngine
from tests.helpers import (
    expert_forms_on_record, pool_stat_keys_are, zero_cache)

EXACT = precision.Exact()
PUBLISHED_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}


def tiny(**kw):
    kw.setdefault("dtype", jnp.float32)
    return MellumConfig.tiny(**kw)


def reference_config(cfg):
    """The configuration file's keys the reference reads, from the
    program's configuration object."""
    return dict(
        num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        sliding_window=cfg.sliding_window, layer_types=list(cfg.layer_types),
        rms_norm_eps=cfg.rms_norm_eps,
        rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                "factor": cfg.rope_factor,
                "original_max_position_embeddings":
                    cfg.original_max_position_embeddings,
                "beta_fast": cfg.beta_fast, "beta_slow": cfg.beta_slow,
                "attention_factor": cfg.attention_factor},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg.rope_theta}},
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob, num_experts=cfg.held,
        first_expert_held=cfg.first_expert,
        parameter_dtype=jnp.dtype(cfg.dtype).name)


@functools.cache
def drawn_params(cfg, seed=3):
    module = Mellum(cfg)
    abstract = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    keys = iter(jax.random.split(jax.random.key(seed), 100))

    def one(path, leaf):
        noise = jax.random.normal(next(keys), leaf.shape, jnp.float32)
        value = 1.0 + 0.1 * noise if path[-1].key == "scale" else 0.05 * noise
        return value.astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(one, abstract)


def tokens_of(cfg, shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, shape), jnp.int32)


@functools.cache
def _reference(cfg, dot):
    """One program a configuration and shape: run op by op the reference
    compiled its layer scans anew at every call."""
    return jax.jit(lambda f32, tokens: ref.logits(
        dot, reference_config(cfg), f32, tokens))


def reference_logits(cfg, params, tokens, dot=EXACT):
    f32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    return np.asarray(_reference(cfg, dot)(f32, tokens))


# -- the full forward pass -----------------------------------------------------

# float32: the same products in another order.  bfloat16, two numbers: a
# logit of size about 1 moves by 0.001-0.0015 in the mean over six draws
# (operands of 8 significant bits through 4 layers), and by 0.007 at the most
# where no router choice flips but up to 0.1 where one does (a near tie
# decided by the last bit, which moves one expert's whole part).  The same
# reference with every product's operands in float8 reads 0.025-0.031 in the
# mean and 0.17-0.29 at the most: the mean's limit is 3 times over the one
# and 5 times under the other, and it is the mean that fails float8.
TOLERANCE = {"float32": (3e-5, 3e-6), "bfloat16": (0.15, 0.005)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_matches_the_reference(dtype):
    cfg = tiny(experts_held=4, first_expert=2, dtype=jnp.dtype(dtype))
    params = drawn_params(cfg)
    tokens = tokens_of(cfg, (3, 60))        # past the window of 24
    got = jax.jit(lambda p, t: Mellum(cfg).apply({"params": p}, t))(
        params, tokens)
    assert got.dtype == jnp.float32
    gap = np.abs(np.asarray(got) - reference_logits(cfg, params, tokens))
    most, mean = TOLERANCE[dtype]
    assert gap.max() <= most and gap.mean() <= mean


def test_a_float8_product_fails_the_bfloat16_tolerance():
    cfg = tiny(experts_held=4, first_expert=2, dtype=jnp.bfloat16)
    params = drawn_params(cfg)
    tokens = tokens_of(cfg, (3, 60))
    exact = reference_logits(cfg, params, tokens)
    low = reference_logits(cfg, params, tokens, precision.Fp8())
    assert np.abs(exact - low).mean() > 4 * TOLERANCE["bfloat16"][1]


@pytest.mark.parametrize("bad,match", [
    (dict(experts_held=0), "experts_held"),
    (dict(experts_held=9), "experts_held"),
    (dict(experts_held=4, first_expert=5), "first_expert"),
    (dict(num_key_value_heads=3), "multiple"),
    (dict(head_dim=15), "even"),
    (dict(layer_types=("sliding_attention", "dense")), "layer_types"),
    (dict(sliding_window=0), "sliding_window"),
])
def test_config_refuses_what_is_no_configuration(bad, match):
    with pytest.raises(ValueError, match=match):
        tiny(**bad)


def test_config_takes_the_published_groups_whole():
    """``rope_parameters`` and a 28-entry ``layer_types`` as config.json has
    them: the numbers land in the flat fields, the list is cut to depth."""
    published = MellumConfig.published()
    types = ["sliding_attention"] * 3 + ["full_attention"]
    given = MellumConfig(rope_parameters=PUBLISHED_ROPE, layer_types=types * 7)
    assert given == published and hash(given) == hash(published)
    assert published.layer_types == tuple(types * 7)
    assert (published.period, published.n_window_layers,
            published.n_full_layers, published.kv_row) == (4, 21, 7, 1024)
    share = MellumConfig.v5e4_share(layer_types=types * 7)
    assert share == MellumConfig.v5e4_share()
    assert (share.num_hidden_layers, share.held, share.vocab_size,
            share.num_experts, share.n_full_layers) == (16, 16, 24576, 64, 4)
    assert dataclasses.replace(
        share, num_hidden_layers=28, vocab_size=98304, experts_held=None,
        layer_types=None) == published
    # A pattern that repeats nothing is one period as long as the model.
    odd = tiny(num_hidden_layers=3, layer_types=(
        "full_attention", "sliding_attention", "sliding_attention"))
    assert odd.period == 3


# -- rotary tables and the banded mask against closed forms -------------------

def test_yarn_table_matches_its_closed_form():
    cfg = MellumConfig.published()
    i = np.arange(64)
    extra = 500000.0 ** (-2 * i / 128)
    pair = lambda b: 64 * math.log(8192 / (2 * math.pi * b)) / math.log(500000)
    low, high = math.floor(pair(32)), math.ceil(pair(1))
    assert (low, high) == (18, 35)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = extra / 16 * ramp + extra * (1 - ramp)
    got = mellum.yarn_inv_freq(cfg)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # Fast pairs untouched, slow pairs divided by the factor.
    np.testing.assert_allclose(got[:19], extra[:19], rtol=1e-6)
    np.testing.assert_allclose(got[35:], extra[35:] / 16, rtol=1e-6)
    np.testing.assert_allclose(mellum.plain_inv_freq(cfg), extra, rtol=1e-6)
    assert cfg.attention_factor == pytest.approx(0.1 * math.log(16) + 1)
    # The reference's own table, written apart, agrees.
    table, factor = ref.rotary_table(
        dict(head_dim=128, rope_parameters=PUBLISHED_ROPE), "full_attention")
    np.testing.assert_allclose(table, want, rtol=1e-6)
    assert factor == cfg.attention_factor
    table, factor = ref.rotary_table(
        dict(head_dim=128, rope_parameters=PUBLISHED_ROPE),
        "sliding_attention")
    np.testing.assert_allclose(table, extra, rtol=1e-6)
    assert factor == 1.0


def test_full_layers_rotate_by_the_scaled_table():
    """The factor is on cos and sin both: a rotated vector is that much
    longer, and the score of two depends on their distance alone."""
    cfg = tiny()
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 1, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 16)), jnp.float32)
    table = dict(inv_freq=mellum.yarn_inv_freq(cfg),
                 scale=cfg.attention_factor)
    at = lambda p: jnp.full((1, 1), p, jnp.int32)
    rot = lambda x, p: parts.rope(x, at(p), cfg.rope_theta, **table)
    assert float(jnp.linalg.norm(rot(q, 9))) == pytest.approx(
        cfg.attention_factor * float(jnp.linalg.norm(q)), rel=1e-5)
    score = lambda pq, pk: float(jnp.sum(rot(q, pq) * rot(k, pk)))
    assert score(7, 3) == pytest.approx(score(104, 100), abs=1e-4)
    assert score(7, 3) != pytest.approx(score(7, 4), abs=1e-3)


@pytest.mark.parametrize("window", [None, 1, 5, 24])
def test_banded_mask_matches_its_closed_form(window):
    q = jnp.asarray([[3, 10, 30, 31]])
    k = jnp.asarray([list(range(-2, 38))])          # -2, -1: never written
    got = np.asarray(parts.attention_mask(q, k, window))[0]
    for a, i in enumerate([3, 10, 30, 31]):
        for b, j in enumerate(range(-2, 38)):
            want = 0 <= j <= i and (window is None or i - j < window)
            assert got[a, b] == want, (i, j)
    if window is not None:       # the window counts the query itself
        assert got.sum(-1).tolist() == [min(i + 1, window)
                                        for i in (3, 10, 30, 31)]


# -- prefill in chunks, then decode, through both pools -----------------------

def paged_for(cfg, *, slots, total, block, chunk, megastep=1):
    per_slot = -(-total // block)
    ring = min(per_slot,
               -(-(cfg.sliding_window + chunk + megastep) // block) + 1)
    paged = PagedKVConfig(
        block_size=block, num_blocks=slots * per_slot + 1,
        window_blocks=slots * ring + 1, window_ring=ring)
    tables = np.zeros((slots, per_slot + ring), np.int32)
    for s in range(slots):
        tables[s, :per_slot] = 1 + s * per_slot + np.arange(per_slot)
        tables[s, per_slot:] = 1 + s * ring + np.arange(ring)
    return paged, jnp.asarray(tables)


@pytest.fixture(params=[False, True], ids=["gather", "kernel"])
def kernel(request, monkeypatch):
    """Whether a decode step's attention runs the block-table kernel (in
    the Pallas interpreter) or, as the CPU does without it, the gather."""
    if request.param:
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("DTT_PALLAS_INTERPRET", raising=False)
    return request.param


GATHERS = {parts.GATHER_WINDOW, parts.GATHER_FULL}
KERNELS = {parts.KERNEL_WINDOW, parts.KERNEL_FULL}


@pytest.mark.parametrize("block,chunk", [(8, 16), (16, 16), (8, 40)])
def test_chunked_prefill_then_decode_gives_the_reference_logits(block, chunk,
                                                                kernel):
    """A row 3 x (window + chunk) long: every position's logits, prefilled
    ``chunk`` at a time and then decoded one by one, against the
    reference's full forward pass.  The ring (window + chunk + 1 positions
    in whole blocks and one block more) wraps, most cells twice; a chunk's
    first query still sees the window's 23 positions before it.  Under the
    interpreter the chunks still gather and the single positions read both
    pools through the kernel, from the window's first position on."""
    cfg = tiny(experts_held=4, first_expert=2)
    total = 3 * (cfg.sliding_window + chunk)
    params = drawn_params(cfg)
    tokens = tokens_of(cfg, (2, total))
    paged, tables = paged_for(cfg, slots=2, total=total, block=block,
                              chunk=chunk)
    assert paged.window_capacity < total * 0.6     # wraps, and again
    module = Mellum(cfg)
    slot_ids = jnp.arange(2, dtype=jnp.int32)
    call = dict(decode=True, slot_ids=slot_ids, paged=paged,
                block_tables=tables)
    cache = zero_cache(module, tokens[:, :chunk], **call)
    step = jax.jit(lambda c, t: module.apply(
        {"params": params, "cache": c}, t, mutable=["cache"], **call))
    got, at = [], 0
    prefilled = 2 * (cfg.sliding_window + chunk) // chunk * chunk
    paths = {chunk: set(), 1: set()}
    while at < total:
        n = chunk if at < prefilled else 1
        with paged_attention.record_paths() as traced:
            out, mutated = step(cache, tokens[:, at:at + n])
        paths[n].update(traced)         # what the call's first trace took
        cache = mutated["cache"]
        got.append(np.asarray(out))
        at += n
    assert paths == {chunk: GATHERS, 1: KERNELS if kernel else GATHERS}
    want = reference_logits(cfg, params, tokens)
    np.testing.assert_allclose(np.concatenate(got, axis=1), want, atol=5e-5)
    assert cache["cache_index"].tolist() == [total, total]
    assert cache["window_pool"].shape == (
        3, paged.window_blocks, block, cfg.kv_row)
    assert cache["full_pool"].shape == (1, paged.num_blocks, block, cfg.kv_row)
    assert cache["moe_counts"].shape == (4, cfg.held + 3)


def test_a_call_longer_than_the_ring_allows_is_refused():
    cfg = tiny()
    paged, tables = paged_for(cfg, slots=1, total=256, block=8, chunk=16)
    module = Mellum(cfg)
    call = dict(decode=True, slot_ids=jnp.zeros((1,), jnp.int32), paged=paged,
                block_tables=tables)
    variables = module.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32),
                            **call)
    long = paged.window_capacity - cfg.sliding_window + 2
    with pytest.raises(ValueError, match="still in the window ring"):
        module.apply(variables, jnp.zeros((1, long), jnp.int32),
                     mutable=["cache"], **call)
    module.apply(variables, jnp.zeros((1, long - 1), jnp.int32),
                 mutable=["cache"], **call)


def test_the_module_has_no_dense_row_cache_and_needs_its_window_pool():
    cfg = tiny()
    module = Mellum(cfg)
    tokens = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="paged only"):
        module.init(jax.random.key(0), tokens, decode=True)
    call = dict(decode=True, slot_ids=jnp.zeros((1,), jnp.int32),
                block_tables=jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="kv_dtype"):
        module.init(jax.random.key(0), tokens, paged=PagedKVConfig(
            block_size=16, num_blocks=5, kv_dtype="int8"), **call)
    with pytest.raises(ValueError, match="window pool"):
        module.init(jax.random.key(0), tokens, paged=PagedKVConfig(
            block_size=16, num_blocks=5), **call)


@pytest.mark.parametrize("bad,match", [
    (dict(window_ring=4), "go together"),
    (dict(window_blocks=9), "go together"),
    (dict(window_ring=4, window_blocks=4), "trash block"),
    (dict(window_ring=4, window_blocks=10, num_blocks=10, data_shards=2),
     "per-shard"),
])
def test_paged_config_refuses_half_a_window_pool(bad, match):
    with pytest.raises(ValueError, match=match):
        PagedKVConfig(**{"block_size": 16, "num_blocks": 9, **bad})


def test_paged_config_splits_the_one_table():
    paged = PagedKVConfig(block_size=16, num_blocks=9, window_blocks=7,
                          window_ring=3)
    assert (paged.table_width(64), paged.window_capacity) == (7, 48)
    table = np.arange(14).reshape(2, 7)
    full, ring = paged.split_tables(table)
    assert full.tolist() == [[0, 1, 2, 3], [7, 8, 9, 10]]
    assert ring.tolist() == [[4, 5, 6], [11, 12, 13]]
    plain = PagedKVConfig(block_size=16, num_blocks=9)
    assert plain.table_width(64) == 4
    assert plain.split_tables(table)[1] is None
    from distributed_tensorflow_tpu.models import gpt2
    assert gpt2.PagedKVConfig is PagedKVConfig     # re-exported, not copied


# -- the expert layer ---------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """Experts 0-1, 2-3, 4-5 and 6-7, each share's part by the program's
    layer: together the uncut reference layer (no shared expert to count
    once).  And each share is the reference's share."""
    whole = tiny()
    params = drawn_params(whole)
    layer = jax.tree.map(lambda w: w[1], params["layers"])
    x = jnp.asarray(np.random.default_rng(4).normal(size=(50, 64)),
                    jnp.float32)
    uncut = ref.expert_ffn(EXACT, reference_config(whole), x, layer)
    total = 0.0
    for first in (0, 2, 4, 6):
        cfg = tiny(experts_held=2, first_expert=first)
        share = dict(layer, experts=jax.tree.map(
            lambda w: w[first:first + 2], layer["experts"]))
        part, row = parts.expert_layer(cfg, share, x)
        np.testing.assert_allclose(
            np.asarray(part), np.asarray(ref.expert_ffn(
                EXACT, reference_config(cfg), x, share)), atol=2e-5)
        assert int(row[:2].sum() + row[2]) == 50 * cfg.num_experts_per_tok
        total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5)


def test_router_is_a_softmax_over_all_experts_renormalised_over_the_chosen():
    cfg = tiny()
    p = jax.tree.map(lambda w: w[0], drawn_params(cfg)["layers"])["router"]
    x = jnp.asarray(np.random.default_rng(5).normal(size=(30, 64)),
                    jnp.float32)
    chosen, weights = route(cfg, p, x)
    probs = np.asarray(jax.nn.softmax(x @ p["kernel"], axis=-1))
    order = np.argsort(-probs, axis=-1)[:, :cfg.num_experts_per_tok]
    assert np.array_equal(np.sort(np.asarray(chosen), -1), np.sort(order, -1))
    picked = np.take_along_axis(probs, np.asarray(chosen), axis=-1)
    np.testing.assert_allclose(np.asarray(weights),
                               picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-5)
    want = np.asarray(ref._route(EXACT, reference_config(cfg), x, p))
    assert np.array_equal(want > 0, (
        np.arange(8)[None, None] == np.asarray(chosen)[:, :, None]).any(1))
    np.testing.assert_allclose(
        np.take_along_axis(want, np.asarray(chosen), axis=-1),
        np.asarray(weights), rtol=1e-5)


# -- through the engine and the continuous scheduler --------------------------

SERVED = tiny(experts_held=4, first_expert=2)
CHUNK = 16


def _engine(**kw):
    eng = ServeEngine("mellum", config=SERVED, **kw)
    eng.install_params(eng.shard_params(drawn_params(SERVED)))
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def engine():
    yield from _engine()


@pytest.fixture(scope="module")
def kernel_engine():
    """An engine keeps the programs it traced, so the one whose decode
    programs are traced under the interpreter is its own; on one device, as
    the kernel asks."""
    from distributed_tensorflow_tpu import cluster as cluster_lib
    yield from _engine(mesh=cluster_lib.build_mesh(
        cluster_lib.MeshConfig(), devices=jax.devices()[:1]))


def scheduler(engine, **kw):
    args = dict(num_slots=2, max_total_len=256, cache_mode="paged",
                block_size=8, prefill_budget=CHUNK, megastep=4,
                async_decode=True)
    args.update(kw)
    return ContinuousScheduler(engine, **args)


def _gap_to_reference_best(engine, prompt, answer):
    seq = np.concatenate([prompt, answer])[None, :-1]
    at = reference_logits(SERVED, engine.params, jnp.asarray(seq))[
        0, len(prompt) - 1:]
    return at.max(-1) - at[np.arange(len(answer)), answer]


@pytest.mark.parametrize("async_decode", [False, True])
@pytest.mark.parametrize("megastep", [1, 4])
def test_scheduler_serves_the_reference_best_tokens(request, monkeypatch,
                                                    kernel, megastep,
                                                    async_decode):
    """Greedy answers through both pools, rows longer than the window and
    than the ring, every token the reference's own first choice: with the
    decode programs on the gather paths, and on the kernel's (every prefill
    program on the gather paths either way)."""
    engine = request.getfixturevalue("kernel_engine" if kernel else "engine")
    before = engine.decode_attention_launches()
    rng = np.random.default_rng(0)
    requests = [(rng.integers(0, SERVED.vocab_size, n, dtype=np.int32), new)
                for n, new in ((48, 100), (32, 30), (80, 150), (16, 8))]
    with scheduler(engine, megastep=megastep,
                   async_decode=async_decode) as sched:
        ring = sched.paged.window_ring
        assert ring == -(-(24 + CHUNK + megastep) // 8) + 1
        futures = [sched.submit(p, max_new_tokens=n) for p, n in requests]
        answers = [np.asarray(f.result(timeout=600)) for f in futures]
        # The launch counter is the process's: this scheduler's share of
        # it is what it added.
        total = engine.decode_attention_launches
        with monkeypatch.context() as patch:
            patch.setattr(
                engine, "decode_attention_launches",
                lambda: {path: n - before[path]
                         for path, n in total().items()})
            stats = sched.stats()
    assert stats["decode_attention_kernel_share"] == float(kernel)
    assert stats["moe_layer_steps"] > 0 and stats["moe_experts_held"] == 4
    assert stats["window_blocks_recycled"] > 0
    assert (stats["decode_live_positions_window"]
            < stats["decode_live_positions"])
    for (prompt, new), answer in zip(requests, answers):
        assert len(answer) == new
        assert _gap_to_reference_best(engine, prompt, answer).max() <= 1e-4
    paths = engine.attention_paths()
    decode = KERNELS if kernel else GATHERS
    assert set(paths["slot_prefill"]) == GATHERS
    assert set(paths["slot_megastep"]) == decode
    launches = {path: n - before[path] for path, n
                in engine.decode_attention_launches().items()}
    assert {path for path, n in launches.items() if n} == decode
    assert len({launches[path] for path in decode}) == 1


def test_stats_name_the_form_each_programs_expert_layers_took(engine):
    """Chunks of 16 tokens of 2-of-8 (the dense form) and 2 slots a decode
    step (the grouped form), through both pools."""
    prompt = np.random.default_rng(2).integers(
        0, SERVED.vocab_size, 2 * CHUNK, dtype=np.int32)
    with scheduler(engine) as sched:
        sched.submit(prompt, max_new_tokens=6).result(timeout=300)
        expert_forms_on_record(sched, experts=SERVED.num_experts,
                               chunk=CHUNK)


def _held(sched):
    ring = sched.paged.window_ring
    table = sched._block_tables
    return (int((table[:, :-ring] > 0).sum()),
            int((table[:, -ring:] > 0).sum()))


def test_a_long_row_holds_the_rings_blocks_and_no_more(engine):
    """A row 3 x (window + chunk) long: its full-pool blocks grow with it,
    its window blocks stop at the ring; retirement returns both kinds."""
    total = 3 * (SERVED.sliding_window + CHUNK)
    prompt = np.random.default_rng(1).integers(
        0, SERVED.vocab_size, 2 * CHUNK, dtype=np.int32)
    seen = []
    with scheduler(engine, start=False) as sched:
        ring, geometry = sched.paged.window_ring, sched._kv_geometry
        future = sched.submit(prompt, max_new_tokens=total - len(prompt),
                              on_token=lambda toks: seen.append(
                                  (_held(sched), sched.stats())))
        sched._thread.start()
        answer = future.result(timeout=600)
        assert len(answer) == total - len(prompt)
        after = sched.stats()
        assert _held(sched) == (0, 0)
        assert (sched._block_tables == 0).all()
    assert after["kv_blocks_held_full"] == after["kv_blocks_held_window"] == 0
    assert after["window_blocks_recycled"] == -(-(total - 1) // 8) - ring
    held_full = [h[0] for h, _ in seen]
    held_window = [h[1] for h, _ in seen]
    assert max(held_window) == ring and held_window[-1] == ring
    assert max(held_full) == -(-(total - 1) // 8) > 2 * ring
    # While the row is short both kinds hold the same blocks; once it is
    # long the window layers hold the ring and the bytes held fall under
    # what one geometry for every layer would hold.
    (full, window), stats = next(
        (h, s) for h, s in seen
        if h[0] > ring and h[0] == s["kv_blocks_held_full"])
    assert stats["kv_blocks_held_window"] == ring
    assert stats["kv_bytes_held"] == (full * geometry["full_block_bytes"]
                                      + ring * geometry["window_block_bytes"])
    assert stats["kv_bytes_held_uniform"] == full * (
        geometry["full_block_bytes"] + geometry["window_block_bytes"])
    assert stats["kv_bytes_held"] < stats["kv_bytes_held_uniform"]
    gauge = default_registry().gauge(
        "dtt_serve_kv_blocks_held", labelnames=("kind",))
    assert gauge.labels(kind="window").value == 0
    assert gauge.labels(kind="full").value == 0


def test_cancel_returns_both_kinds_of_block(engine):
    prompt = np.random.default_rng(2).integers(
        0, SERVED.vocab_size, 3 * CHUNK, dtype=np.int32)
    with scheduler(engine) as sched:
        started = []
        future = sched.submit(prompt, max_new_tokens=200,
                              on_token=started.append)
        deadline = 600
        t0 = time.monotonic()
        while not started and time.monotonic() - t0 < deadline:
            time.sleep(0.01)
        assert _held(sched)[0] > 0 and _held(sched)[1] > 0
        assert sched.cancel(future.rid)
        while (sched.stats()["active_slots"]
               and time.monotonic() - t0 < deadline):
            time.sleep(0.01)
        assert _held(sched) == (0, 0)
        stats = sched.stats()
        assert stats["kv_blocks_held_full"] == 0
        assert stats["kv_blocks_held_window"] == 0
        assert stats["blocks_in_use"] == 0
        # The slot serves the next request from a clean ring.
        again = sched.submit(prompt[:CHUNK], max_new_tokens=40)
        answer = np.asarray(again.result(timeout=600))
    assert _gap_to_reference_best(engine, prompt[:CHUNK], answer).max() <= 1e-4


def test_unchunked_prefill_gets_a_ring_as_long_as_the_row(engine):
    """``prefill_budget=0`` prefills a prompt in one call, so the ring is
    sized for the longest row and never wraps."""
    prompt = np.random.default_rng(3).integers(
        0, SERVED.vocab_size, 70, dtype=np.int32)
    with scheduler(engine, prefill_budget=0, max_total_len=128) as sched:
        assert sched.paged.window_ring == 128 // 8
        answer = np.asarray(sched.submit(prompt, max_new_tokens=20).result(
            timeout=600))
    assert _gap_to_reference_best(engine, prompt, answer).max() <= 1e-4


REFUSED = {
    "dense_cache": dict(cache_mode="dense"),
    "kv_dtype": dict(kv_dtype="int8"),
    "per_shard_kv": dict(per_shard_kv=True),
    "slo_scheduling": dict(slo_scheduling=True),
    "spec_k": dict(spec_k=2),
    "prefix_cache": dict(prefix_cache=True),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_scheduler_refuses_what_two_pools_cannot_serve(engine, feature):
    kwargs = dict(num_slots=2, max_total_len=64, cache_mode="paged",
                  block_size=16, start=False)
    kwargs.update(REFUSED[feature])
    reason = mellum.SERVE_REFUSALS[feature]
    with pytest.raises(ValueError) as refused:
        ContinuousScheduler(engine, **kwargs)
    assert feature in str(refused.value) and reason in str(refused.value)


def test_a_tensor_mesh_is_refused_with_its_reason(mesh_2d):
    with pytest.raises(ValueError, match="tensor"):
        ServeEngine("mellum", mesh=mesh_2d, config=SERVED)


def test_stats_hold_the_rings_keys_and_no_other_pools(engine):
    pool_stat_keys_are(engine, "ring")


def test_engine_reports_both_kinds_of_pool(engine):
    paged = PagedKVConfig(block_size=8, num_blocks=65, window_blocks=15,
                          window_ring=7)
    geometry = engine.cache_geometry(paged)
    assert geometry == mellum.cache_geometry(SERVED, paged)
    row = SERVED.kv_row * 4                         # float32 here
    assert geometry["kind"] == "key_value_grouped"
    assert (geometry["full_layers"], geometry["window_layers"],
            geometry["window_positions"]) == (1, 3, 24)
    assert geometry["bytes_per_token_layer"] == row
    assert geometry["bytes_per_token"] == 4 * row
    assert geometry["bytes_per_token_past_window"] == row
    assert geometry["full_pool_bytes"] == 65 * 8 * row
    assert geometry["window_pool_bytes"] == 3 * 15 * 8 * row
    assert geometry["window_ring_positions"] == 56
    assert geometry["pool_bytes"] == (geometry["full_pool_bytes"]
                                      + geometry["window_pool_bytes"])
    # The other families have one kind and say nothing of a window.
    gpt2 = get_workload("gpt2", preset="tiny").cache_geometry(
        PagedKVConfig(block_size=16, num_blocks=9))
    assert "window_positions" not in gpt2


def test_other_families_keep_one_table_and_no_window_stats():
    with ServeEngine("gpt2", preset="tiny") as eng, ContinuousScheduler(
            eng, num_slots=2, max_total_len=64, cache_mode="paged",
            block_size=16, start=False) as sched:
        assert sched.paged.window_ring == 0
        assert sched._block_tables.shape == (sched.num_slots, 4)
        assert "kv_bytes_held" not in sched.stats()


# -- through serve.py's driver -------------------------------------------------

def test_the_serve_driver_takes_the_family():
    from distributed_tensorflow_tpu.serve.driver import (
        DECODER_MODELS, ServeArgs, run_serve)

    assert "mellum" in DECODER_MODELS
    out = run_serve(ServeArgs(
        model="mellum", continuous=True, cache_mode="paged", num_slots=4,
        steps=6, megastep=4, async_decode=True, prefill_budget=16))
    assert out["model"] == "mellum" and out["preset"] == "tiny"
    assert out["completed"] == 6 and out["compile_post_warmup"] == 0
    assert out["cache_mode"] == "paged" and out["tokens_generated"] > 0


def test_the_serve_driver_refuses_the_fixed_batch_path_with_the_reason():
    from distributed_tensorflow_tpu.serve.driver import ServeArgs, run_serve

    with pytest.raises(ValueError, match="--continuous --cache_mode=paged"):
        run_serve(ServeArgs(model="mellum", steps=2))
