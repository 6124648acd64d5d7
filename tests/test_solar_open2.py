"""The linear-attention, sparse-expert decoder family
(``models/solar_open2.py``) at a tiny size on the CPU, against the
benchmark's plain reference (``benchmark/reference/solar_open2.py``: full
forward pass, the delta rule a position at a time, no cache, float32).

What is held here: the full forward pass and, through the paged pool and the
per-slot state, prefill in chunks and then decode give the reference's
logits; the chunk-wise rule equals the recurrence at every chunk boundary;
the sixteen shares of the expert layer add up to the uncut layer; a row that
is free, finished inside a megastep or between two prefill chunks keeps its
state bit for bit across decode launches, and a reused or cancelled slot
serves as a fresh one does; every scheduler feature the family cannot serve
is refused with its reason; a float8 product fails the bfloat16 tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import precision
from benchmark.reference import solar_open2 as ref
from distributed_tensorflow_tpu.models import PagedKVConfig, get_workload
from distributed_tensorflow_tpu.models import decoder_parts as parts
from distributed_tensorflow_tpu.models import solar_open2 as so
from distributed_tensorflow_tpu.models.solar_open2 import (
    SolarOpen2, SolarOpen2Config)
from distributed_tensorflow_tpu.obs.metrics import default_registry
from distributed_tensorflow_tpu.serve import ContinuousScheduler, ServeEngine
from tests.helpers import expert_forms_on_record, pool_stat_keys_are

EXACT = precision.Exact()
PUBLISHED_GROUP = {"short_conv_kernel_size": 4, "head_dim": 128,
                   "num_heads": 64, "num_kv_heads": None}


def tiny(**kw):
    kw.setdefault("dtype", jnp.float32)
    return SolarOpen2Config.tiny(**kw)


def reference_config(cfg):
    """The configuration file's keys the reference reads, from the
    program's configuration object."""
    return dict(
        num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        gqa_layers=list(range(0, 48, 4)), rms_norm_eps=cfg.rms_norm_eps,
        linear_attn_config={
            "short_conv_kernel_size": cfg.kda_conv_size,
            "head_dim": cfg.kda_head_dim, "num_heads": cfg.kda_num_heads,
            "num_kv_heads": None},
        kda_allow_neg_eigval=cfg.kda_allow_neg_eigval,
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        n_routed_experts=cfg.held, first_expert_held=cfg.first_expert,
        parameter_dtype=jnp.dtype(cfg.dtype).name)


def drawn_params(cfg, seed=3):
    module = SolarOpen2(cfg)
    abstract = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    keys = iter(jax.random.split(jax.random.key(seed), 200))

    def one(path, leaf):
        noise = jax.random.normal(next(keys), leaf.shape, jnp.float32)
        value = 1.0 + 0.1 * noise if path[-1].key == "scale" else 0.05 * noise
        return value.astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(one, abstract)


def tokens_of(cfg, shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, shape), jnp.int32)


def reference_logits(cfg, params, tokens, dot=EXACT):
    f32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    return np.asarray(ref.logits(dot, reference_config(cfg), f32, tokens))


# -- the full forward pass -----------------------------------------------------

# float32: the same products in another order, and the chunk-wise rule in
# the recurrence's place (2.5e-5 at the most over 8 layers).  bfloat16, two
# numbers, at one period of four layers: a logit of size about 2 moves by
# 0.014 in the mean (a linear layer passes on about three times the error it
# is given: its heads' outputs are small sums that the head norm scales up;
# 0.0012 after the first layer, 0.004 after the second) and by 1.1 at the
# most, where a router's near tie flips.  The same reference with every
# product's operands in float8 reads 0.127 in the mean: the mean's limit is
# twice over the one and 4 times under the other, and it is the mean that
# fails float8.
TOLERANCE = {"float32": (1e-4, 1e-5), "bfloat16": (2.0, 0.03)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_matches_the_reference(dtype):
    cfg = tiny(experts_held=4, first_expert=2, dtype=jnp.dtype(dtype),
               num_hidden_layers=8 if dtype == "float32" else 4)
    params = drawn_params(cfg)
    tokens = tokens_of(cfg, (3, 100))       # a whole chunk of 64 and a part
    got = SolarOpen2(cfg).apply({"params": params}, tokens)
    assert got.dtype == jnp.float32
    gap = np.abs(np.asarray(got) - reference_logits(cfg, params, tokens))
    most, mean = TOLERANCE[dtype]
    assert gap.max() <= most and gap.mean() <= mean


def test_a_float8_product_fails_the_bfloat16_tolerance():
    cfg = tiny(experts_held=4, first_expert=2, dtype=jnp.bfloat16,
               num_hidden_layers=4)
    params = drawn_params(cfg)
    tokens = tokens_of(cfg, (3, 100))
    exact = reference_logits(cfg, params, tokens)
    low = reference_logits(cfg, params, tokens, precision.Fp8())
    assert np.abs(exact - low).mean() > 3 * TOLERANCE["bfloat16"][1]


def test_bfloat16_fails_the_float32_tolerance():
    cfg = tiny(experts_held=4, first_expert=2, dtype=jnp.bfloat16,
               num_hidden_layers=4)
    params = drawn_params(cfg)
    tokens = tokens_of(cfg, (3, 100))
    got = SolarOpen2(cfg).apply({"params": params}, tokens)
    gap = np.abs(np.asarray(got) - reference_logits(cfg, params, tokens))
    assert gap.mean() > 100 * TOLERANCE["float32"][1]


@pytest.mark.parametrize("bad,match", [
    (dict(experts_held=0), "experts_held"),
    (dict(experts_held=9), "experts_held"),
    (dict(experts_held=4, first_expert=5), "first_expert"),
    (dict(num_key_value_heads=3), "multiple"),
    (dict(gqa_layers=(4, 0)), "gqa_layers"),
    (dict(use_rope=True), "use_rope"),
    (dict(use_gqa_gate=False), "use_gqa_gate"),
    (dict(kda_use_full_proj=True), "kda_use_full_proj"),
    (dict(first_k_dense_replace=1), "first_k_dense_replace"),
    (dict(linear_attn_config=dict(PUBLISHED_GROUP, num_kv_heads=8)),
     "num_kv_heads"),
])
def test_config_refuses_what_is_no_configuration(bad, match):
    with pytest.raises(ValueError, match=match):
        tiny(**bad)


def test_config_takes_the_published_group_whole_and_cuts_the_list():
    cfg = SolarOpen2Config(
        linear_attn_config=PUBLISHED_GROUP, num_hidden_layers=4,
        gqa_layers=list(range(0, 48, 4)), experts_held=20, vocab_size=24576)
    assert (cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_conv_size) == (
        64, 128, 4)
    assert cfg.linear_attn_config is None and cfg.kda_gate_rank == 128
    assert cfg.gqa_layers == (0,) and cfg.period == 4
    assert (cfg.n_gqa_layers, cfg.n_kda_layers) == (1, 3)
    assert cfg == SolarOpen2Config.v5e128_share()
    whole = SolarOpen2Config.published()
    assert whole.gqa_layers == tuple(range(0, 48, 4)) and whole.period == 4
    assert whole.kinds[:5] == (True, False, False, False, True)


# -- the chunk-wise rule against the recurrence ------------------------------

def kda_operands(shape, seed, steepest=2.0):
    """Operands of the rule at its published ranges: unit keys, scaled unit
    queries, a log decay down to ``-steepest`` a position (at which a
    product split at the chunk's first position would overflow float32
    after 45 positions), steps in 0..2."""
    B, T, H, D = shape
    r = np.random.default_rng(seed)
    draw = lambda *s: jnp.asarray(r.normal(size=s), jnp.float32)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q, k, v = unit(draw(*shape)) * D ** -0.5, unit(draw(*shape)), draw(*shape)
    log_decay = -jnp.asarray(r.uniform(0, steepest, shape), jnp.float32)
    beta = jnp.asarray(r.uniform(0, 2, (B, T, H)), jnp.float32)
    return q, k, v, log_decay, beta


@pytest.mark.parametrize("chunk", [64, 128, 40, 100, 1])
def test_chunkwise_rule_equals_the_recurrence_at_every_boundary(chunk):
    """A run of 230 positions (70 of them one by one) taken ``chunk`` at a
    time (multiples of the rule's own 64 and not, a partial last chunk; 1 is
    the decode step's form), each call from the state the one before left:
    outputs and the state after every call against the reference's
    position-by-position scan from a state that is not zero.  Float32
    throughout: the two forms differ by the order of their sums."""
    shape = (2, 230 if chunk > 1 else 70, 3, 16)
    q, k, v, log_decay, beta = kda_operands(shape, seed=1)
    start = jnp.asarray(np.random.default_rng(2).normal(
        size=(2, 3, 16, 16)), jnp.float32)
    plain = jax.jit(lambda *a: ref.kda_recurrence(EXACT, *a))
    if chunk == 1:
        ours = jax.jit(lambda s, *a: (
            lambda out, s: (out[:, None], s))(
                *so.kda_step(s, *(x[:, 0] for x in a))))
    else:
        ours = jax.jit(so.kda_chunk)
    state, held, at = start, start, 0
    while at < shape[1]:
        cut = slice(at, min(at + chunk, shape[1]))
        part = [a[:, cut] for a in (q, k, v, log_decay, beta)]
        out, state = ours(state, *part)
        want, held = plain(*part[:3], jnp.exp(part[3]), part[4], held)
        at = cut.stop
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(state), np.asarray(held),
                                   atol=2e-5)


def test_the_rule_in_bfloat16_fails_the_boundary_tolerance():
    shape = (2, 128, 3, 16)
    operands = kda_operands(shape, seed=1)
    zero = jnp.zeros((2, 3, 16, 16), jnp.float32)
    exact, _ = so.kda_chunk(zero, *operands)
    rounded, _ = so.kda_chunk(zero, *operands, jnp.bfloat16)
    assert np.abs(np.asarray(exact - rounded)).max() > 20 * 2e-5


# -- through the cache: chunks, then single positions --------------------------

def paged_for(*, slots, total, block):
    per_slot = -(-total // block)
    paged = PagedKVConfig(block_size=block, num_blocks=slots * per_slot + 1)
    tables = 1 + np.arange(slots * per_slot, dtype=np.int32).reshape(
        slots, per_slot)
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


@pytest.mark.parametrize("block,chunk,kernel", [
    (8, 16, False), (16, 64, False), (8, 40, True)],
    indirect=["kernel"], ids=["8-16-gather", "16-64-gather", "8-40-kernel"])
def test_chunked_prefill_then_decode_gives_the_reference_logits(block, chunk,
                                                                kernel):
    """Every position's logits, prefilled ``chunk`` at a time (a last chunk
    that is partial) and then decoded one by one, against the reference's
    full forward pass.  The cache starts FULL OF GARBAGE: a chunk at
    position 0 starts state and tail from zero whatever the slot held."""
    cfg = tiny(experts_held=4, first_expert=2)
    total, prefilled = 150, 100
    params = drawn_params(cfg)
    tokens = tokens_of(cfg, (2, total))
    paged, tables = paged_for(slots=2, total=total, block=block)
    module = SolarOpen2(cfg)
    call = dict(decode=True, slot_ids=jnp.arange(2, dtype=jnp.int32),
                paged=paged, block_tables=tables)
    cache = module.init(jax.random.key(0), tokens[:, :chunk], **call)["cache"]
    noise = np.random.default_rng(5)
    cache = {name: (jnp.asarray(noise.normal(size=leaf.shape), leaf.dtype)
                    if name.startswith("kda_") else jnp.zeros_like(leaf))
             for name, leaf in cache.items()}
    step = jax.jit(lambda c, t, live: module.apply(
        {"params": params, "cache": c}, t, mutable=["cache"], live=live,
        **call))
    got, at = [], 0
    paths = {}
    while at < total:
        n = min(chunk, prefilled - at) if at < prefilled else 1
        with so.paged_attention.record_paths() as traced:
            out, mutated = step(cache, tokens[:, at:at + n],
                                None if n > 1 else jnp.ones((2,), bool))
        paths.setdefault(n > 1, set()).update(traced)
        cache = mutated["cache"]
        got.append(np.asarray(out))
        at += n
    assert paths[True] == {parts.GATHER_FULL, so.KDA_CHUNK_PATH}
    assert paths[False] == {parts.KERNEL_FULL if kernel else parts.GATHER_FULL,
                            so.KDA_STEP_PATH}
    want = reference_logits(cfg, params, tokens)
    np.testing.assert_allclose(np.concatenate(got, axis=1), want, atol=1e-4)
    assert cache["cache_index"].tolist() == [total, total]
    assert cache["kda_state"].shape == (6, 2, 4, 16, 16)
    assert cache["kda_state"].dtype == jnp.float32
    assert cache["kda_conv"].shape == (6, 2, 3, 3 * 64)
    assert cache["full_pool"].shape == (2, paged.num_blocks, block,
                                        cfg.kv_row)
    assert cache["moe_counts"].shape == (8, cfg.held + 3)


def test_the_module_has_no_dense_row_cache_and_runs_all_slots_in_place():
    cfg = tiny()
    tokens = tokens_of(cfg, (2, 8))
    module = SolarOpen2(cfg)
    with pytest.raises(ValueError, match="paged only"):
        module.init(jax.random.key(0), tokens, decode=True)
    paged, tables = paged_for(slots=2, total=32, block=8)
    call = dict(decode=True, paged=paged, block_tables=tables)
    variables = module.init(jax.random.key(0), tokens,
                            slot_ids=jnp.arange(2), **call)
    with pytest.raises(ValueError, match="all 2 slots in order"):
        module.apply(variables, tokens[:1, :1], slot_ids=jnp.arange(1),
                     live=jnp.ones((1,), bool), mutable=["cache"], **call)
    with pytest.raises(ValueError, match="kv_dtype"):
        module.init(jax.random.key(0), tokens, slot_ids=jnp.arange(2),
                    decode=True, block_tables=tables,
                    paged=PagedKVConfig(block_size=8, num_blocks=9,
                                        kv_dtype="int8"))


# -- the expert layer ---------------------------------------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen experts, one a share, each share's part by the program's
    layer with the shared expert in every one: together, with the shared
    expert counted once, the uncut reference layer.  And each share is the
    reference's share."""
    whole = tiny(n_routed_experts=16, num_experts_per_tok=4)
    params = drawn_params(whole)
    layer = jax.tree.map(lambda w: w[1], params["layers"])
    x = jnp.asarray(np.random.default_rng(4).normal(size=(50, 64)),
                    jnp.float32)
    uncut = ref.expert_ffn(EXACT, reference_config(whole), x, layer)
    shared = ref._mlp(EXACT, x, layer["shared"])
    total = 0.0
    for first in range(16):
        cfg = tiny(n_routed_experts=16, num_experts_per_tok=4,
                   experts_held=1, first_expert=first)
        share = dict(layer, experts=jax.tree.map(
            lambda w: w[first:first + 1], layer["experts"]))
        part, row = parts.expert_layer(cfg, share, x)
        np.testing.assert_allclose(
            np.asarray(part), np.asarray(ref.expert_ffn(
                EXACT, reference_config(cfg), x, share)), atol=2e-5)
        assert int(row[0] + row[1]) == 50 * cfg.num_experts_per_tok
        total = total + part
    np.testing.assert_allclose(np.asarray(total - 15 * shared),
                               np.asarray(uncut), atol=5e-5)


# -- through the engine: what a decode launch may not touch --------------------

SERVED = tiny(experts_held=4, first_expert=2)
CHUNK = 16


def _engine(**kw):
    eng = ServeEngine("solar_open2", config=SERVED, **kw)
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


def _state_rows(cache):
    """slot -> the bytes of its state and convolution tail."""
    state, conv = np.asarray(cache["kda_state"]), np.asarray(cache["kda_conv"])
    return {s: (state[:, s].tobytes(), conv[:, s].tobytes())
            for s in range(state.shape[1])}


@pytest.fixture(scope="module")
def launches(engine):
    """Eight slots (the CPU's eight devices share the rows): 0 and 1
    prefilled (two chunks) and decoding, 2 between its two prefill chunks, 3
    free after an earlier occupant, the rest never used.  Then one launch
    of each decode program, and the same megastep cut short."""
    paged, tables = paged_for(slots=8, total=96, block=8)
    prompts = np.array(tokens_of(SERVED, (4, 2 * CHUNK), seed=7))
    prompts[2] = prompts[0]
    kw = dict(paged=paged, block_tables=np.asarray(tables))

    def prepared():
        cache = engine.init_paged_cache(8, 96, paged=paged)
        last = np.zeros((8,), np.int32)
        for slot, chunks in ((3, 2), (0, 2), (1, 2), (2, 1)):
            for c in range(chunks):
                nxt, cache = engine.prefill_into_slots(
                    cache, prompts[slot:slot + 1, c * CHUNK:(c + 1) * CHUNK],
                    [slot], start_offsets=[c * CHUNK] if c else None, **kw)
            last[slot] = int(np.asarray(nxt)[0])
        return cache, last

    out = {}
    cache, last = prepared()
    out["before"] = _state_rows(cache)
    rows = lambda *head: np.array(list(head) + [0] * (8 - len(head)))
    _, cache = engine.decode_slots(cache, last, rows(1).astype(bool), **kw)
    out["decode_slots"] = _state_rows(cache)
    active = rows(1, 1).astype(bool)
    cache, last = prepared()
    toks, _, steps_run, cache = engine.decode_megastep(
        cache, last, active, rows(4, 2), steps=4, **kw)
    assert int(steps_run) == 4
    out["megastep"] = _state_rows(cache)
    # Slot 2's second chunk, after the launches ran over its row: the token
    # slot 0, with the same prompt, was given.
    nxt, cache = engine.prefill_into_slots(
        cache, prompts[2:3, CHUNK:], [2], start_offsets=[CHUNK], **kw)
    out["second_chunk"] = (int(np.asarray(nxt)[0]), int(last[0]))
    # Slot 3 again, from position 0, over what its last occupant left.
    nxt, cache = engine.prefill_into_slots(
        cache, prompts[1:2, :CHUNK], [3], **kw)
    out["reused"] = _state_rows(cache)[3]
    fresh = engine.init_paged_cache(8, 96, paged=paged)
    _, fresh = engine.prefill_into_slots(
        fresh, prompts[1:2, :CHUNK], [3], **kw)
    out["fresh"] = _state_rows(fresh)[3]
    cache, last = prepared()
    _, _, steps_run, cache = engine.decode_megastep(
        cache, last, active, rows(2, 2), steps=2, **kw)
    assert int(steps_run) == 2
    out["megastep_cut"] = _state_rows(cache)
    return out


@pytest.mark.parametrize("row,slot,launch,same_as", [
    ("a free row", 3, "decode_slots", "before"),
    ("a free row", 3, "megastep", "before"),
    ("a row between two prefill chunks", 2, "decode_slots", "before"),
    ("a row between two prefill chunks", 2, "megastep", "before"),
    ("a row that is not active", 1, "decode_slots", "before"),
    ("a row finished inside the megastep", 1, "megastep", "megastep_cut"),
])
def test_a_row_that_does_not_step_keeps_its_state_bit_for_bit(
        launches, row, slot, launch, same_as):
    assert launches[launch][slot] == launches[same_as][slot], row
    # ... and the rows that did step moved.
    assert launches[launch][0] != launches["before"][0]


def test_a_second_chunk_starts_from_its_first_chunks_state(launches):
    got, want = launches["second_chunk"]
    assert got == want


def test_a_reused_slot_starts_from_zero(launches):
    assert launches["reused"] == launches["fresh"]
    assert launches["reused"] != launches["before"][3]


# -- through the continuous scheduler ------------------------------------------

def scheduler(engine, **kw):
    args = dict(num_slots=2, max_total_len=256, cache_mode="paged",
                block_size=8, prefill_budget=CHUNK, megastep=4,
                async_decode=True)
    args.update(kw)
    return ContinuousScheduler(engine, **args)


_padded_reference = jax.jit(lambda params, tokens: ref.logits(
    EXACT, reference_config(SERVED), params, tokens))


def _gap_to_reference_best(engine, prompt, answer):
    """One compile for every request: the rows are padded to 128 positions
    (what comes after a position does not reach it)."""
    seq = np.concatenate([prompt, answer])[:-1]
    row = np.zeros((1, 128), np.int32)
    row[0, :len(seq)] = seq
    at = np.asarray(_padded_reference(engine.params, jnp.asarray(row)))[
        0, len(prompt) - 1:len(seq)]
    return at.max(-1) - at[np.arange(len(answer)), answer]


@pytest.mark.parametrize("kernel,megastep,async_decode", [
    (False, 1, False), (True, 4, False), (True, 4, True)],
    indirect=["kernel"])
def test_scheduler_serves_the_reference_best_tokens(request, kernel,
                                                    megastep, async_decode):
    """Greedy answers through the pool and the state, five requests (over
    two slots on the kernel's one device: every slot reused, rows finishing
    inside a megastep while their neighbour decodes on; over the CPU's
    eight devices' eight slots otherwise), a prompt whose last chunk is
    partial, every token the reference's own first choice."""
    engine = request.getfixturevalue("kernel_engine" if kernel else "engine")
    resets = default_registry().counter("dtt_serve_state_resets_total")
    resets_before = resets.value
    rng = np.random.default_rng(0)
    requests = [(rng.integers(0, SERVED.vocab_size, n, dtype=np.int32), new)
                for n, new in ((48, 30), (32, 9), (70, 41), (16, 6), (33, 18))]
    with scheduler(engine, megastep=megastep,
                   async_decode=async_decode) as sched:
        futures = [sched.submit(p, max_new_tokens=n) for p, n in requests]
        answers = [np.asarray(f.result(timeout=600)) for f in futures]
        stats = sched.stats()
    geometry = so.cache_geometry(SERVED, sched.paged)
    assert stats["state_bytes_per_slot"] == geometry["state_bytes_per_slot"]
    assert 0 < stats["state_slots_live"] == pytest.approx(
        stats["slot_occupancy"] * stats["num_slots"])
    assert stats["state_resets"] == 5 == resets.value - resets_before
    assert stats["state_bytes_held"] == 0 and stats["kv_bytes_held"] == 0
    assert stats["moe_layer_steps"] > 0 and stats["moe_experts_held"] == 4
    for (prompt, new), answer in zip(requests, answers):
        assert len(answer) == new
        assert _gap_to_reference_best(engine, prompt, answer).max() <= 2e-4
    paths = engine.attention_paths()
    full = parts.KERNEL_FULL if kernel else parts.GATHER_FULL
    assert {parts.GATHER_FULL, so.KDA_CHUNK_PATH} <= set(paths["slot_prefill"])
    assert set(paths["slot_megastep"]) == {full, so.KDA_STEP_PATH}


def test_stats_name_the_form_each_programs_expert_layers_took(engine):
    prompt = np.random.default_rng(2).integers(
        0, SERVED.vocab_size, 2 * CHUNK, dtype=np.int32)
    with scheduler(engine) as sched:
        sched.submit(prompt, max_new_tokens=6).result(timeout=300)
        expert_forms_on_record(sched, experts=SERVED.n_routed_experts,
                               chunk=CHUNK)


def test_cancel_in_mid_prefill_leaves_the_slot_reusable(kernel_engine):
    """One slot (one device), its loop turned by hand: a prompt of three chunks is
    cancelled after its first; the next request takes the slot, and its
    answer is the reference's."""
    rng = np.random.default_rng(3)
    whale = rng.integers(0, SERVED.vocab_size, 3 * CHUNK, dtype=np.int32)
    prompt = rng.integers(0, SERVED.vocab_size, CHUNK + 5, dtype=np.int32)
    gauge = default_registry().gauge("dtt_serve_state_bytes_held")
    engine = kernel_engine
    with scheduler(engine, num_slots=1, start=False,
                   async_decode=False) as sched:
        assert sched.num_slots == 1
        doomed = sched.submit(whale, max_new_tokens=8)
        sched._iteration()
        (req,) = sched._active.values()
        assert 0 < req.next_prefill_offset < len(whale)
        assert gauge.value == sched.stats()["state_bytes_per_slot"] > 0
        assert sched.cancel(doomed.rid)
        sched._iteration()
        assert not sched._active and doomed.cancelled()
        assert gauge.value == 0 and sched.stats()["blocks_in_use"] == 0
        again = sched.submit(prompt, max_new_tokens=12)
        for _ in range(40):
            if again.done():
                break
            sched._iteration()
        answer = np.asarray(again.result(timeout=0))
    assert _gap_to_reference_best(engine, prompt, answer).max() <= 2e-4


REFUSED = {
    "dense_cache": dict(cache_mode="dense"),
    "kv_dtype": dict(kv_dtype="int8"),
    "per_shard_kv": dict(per_shard_kv=True),
    "slo_scheduling": dict(slo_scheduling=True),
    "spec_k": dict(spec_k=2),
    "prefix_cache": dict(prefix_cache=True),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_scheduler_refuses_what_a_state_cannot_serve(engine, feature):
    kwargs = dict(num_slots=2, max_total_len=64, cache_mode="paged",
                  block_size=16, start=False)
    kwargs.update(REFUSED[feature])
    reason = so.SERVE_REFUSALS[feature]
    with pytest.raises(ValueError) as refused:
        ContinuousScheduler(engine, **kwargs)
    assert feature in str(refused.value) and reason in str(refused.value)


def test_a_tensor_mesh_is_refused_with_its_reason(mesh_2d):
    with pytest.raises(ValueError, match="tensor"):
        ServeEngine("solar_open2", mesh=mesh_2d, config=SERVED)


def test_stats_hold_the_states_keys_and_no_other_pools(engine):
    pool_stat_keys_are(engine, "state")


def test_engine_reports_bytes_a_slot_beside_bytes_a_token(engine):
    paged = PagedKVConfig(block_size=8, num_blocks=65)
    geometry = engine.cache_geometry(paged)
    assert geometry == so.cache_geometry(SERVED, paged)
    row = SERVED.kv_row * 4                         # float32 here
    assert geometry["kind"] == "recurrent_state_and_key_value"
    assert (geometry["kv_layers"], geometry["state_layers"]) == (2, 6)
    assert geometry["bytes_per_token_layer"] == row
    assert geometry["bytes_per_token"] == 2 * row
    assert geometry["pool_bytes"] == 2 * 65 * 8 * row
    state, tail = 4 * 16 * 16 * 4, 3 * 3 * 64 * 4
    assert geometry["state_bytes_per_slot"] == 6 * (state + tail)
    cache = engine.init_paged_cache(8, 64, paged=paged)
    assert (cache["kda_state"].nbytes + cache["kda_conv"].nbytes
            == 8 * geometry["state_bytes_per_slot"])
    # The published sizes: 12.6 MB of state a slot, 4 KB of K/V a token.
    cut = so.cache_geometry(SolarOpen2Config.v5e128_share(), paged)
    assert cut["state_bytes_per_slot"] == 3 * (64 * 128 * 128 * 4
                                               + 3 * 3 * 8192 * 2)
    assert cut["bytes_per_token"] == 4096
    # The other families say nothing of a state.
    gpt2 = get_workload("gpt2", preset="tiny").cache_geometry(
        PagedKVConfig(block_size=16, num_blocks=9))
    assert "state_bytes_per_slot" not in gpt2


def test_a_float32_checkpoint_is_held_in_the_served_types():
    cfg = tiny(dtype=jnp.bfloat16, num_hidden_layers=4)
    with ServeEngine("solar_open2", config=cfg) as eng:
        checkpoint = jax.tree.map(
            lambda x: np.asarray(x, np.float32), eng.params)
        eng.install_params(eng.shard_params(checkpoint))
        held = {leaf.dtype.name for leaf in jax.tree.leaves(eng.params)}
        assert held == {"bfloat16", "float32"}
        assert eng.params["kda_decay"]["A_log"].dtype == jnp.float32
        assert eng.params["layers"]["router"]["bias"].dtype == jnp.float32
        assert eng.params["kda"]["qkv"]["kernel"].dtype == jnp.bfloat16


# -- through serve.py's driver -------------------------------------------------

def test_the_serve_driver_takes_the_family():
    from distributed_tensorflow_tpu.serve.driver import (
        DECODER_MODELS, ServeArgs, run_serve)

    assert "solar_open2" in DECODER_MODELS
    out = run_serve(ServeArgs(
        model="solar_open2", continuous=True, cache_mode="paged",
        num_slots=4, steps=6, megastep=4, async_decode=True,
        prefill_budget=16))
    assert out["model"] == "solar_open2" and out["preset"] == "tiny"
    assert out["completed"] == 6 and out["compile_post_warmup"] == 0
    assert out["cache_mode"] == "paged" and out["tokens_generated"] > 0


def test_the_serve_driver_refuses_the_fixed_batch_path_with_the_reason():
    from distributed_tensorflow_tpu.serve.driver import ServeArgs, run_serve

    with pytest.raises(ValueError, match="--continuous --cache_mode=paged"):
        run_serve(ServeArgs(model="solar_open2", steps=2))
