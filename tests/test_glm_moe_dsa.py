"""The latent-attention family whose attention reads what a learned indexer
selects (``models/glm_moe_dsa.py``) at a tiny size on the CPU, against the
benchmark's plain reference (``benchmark/reference/glm_moe_dsa.py``: full
forward pass, the whole ``I[t, s]`` matrix, ``lax.top_k``, a mask, float32).

What is held here: the full forward gives the reference's logits; prefill
in chunks and then decode through the two paged pools gives them too, and
reads the reference's selected sets; index scores and selections equal the
reference's; a context under ``index_topk`` is plain causal latent
attention; ``shared`` layers read the ``full`` layer's set and hold no index
keys; the 32 shares of an expert layer add up to the uncut layer; the pools'
geometry and a token's bytes; the scheduler serves the reference's choices
and counts what it reads, and refuses what the family cannot take; a
program that selects the newest positions in the indexer's place fails the
tolerance the sound program passes.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import glm_moe_dsa as ref
from benchmark.reference import precision
from distributed_tensorflow_tpu.models import PagedKVConfig
from distributed_tensorflow_tpu.models import decoder_parts as parts
from distributed_tensorflow_tpu.models import glm_moe_dsa as dsa
from distributed_tensorflow_tpu.models import paged_call
from distributed_tensorflow_tpu.models.glm_moe_dsa import (
    GlmMoeDsa, GlmMoeDsaConfig)
from distributed_tensorflow_tpu.obs.metrics import default_registry
from distributed_tensorflow_tpu.serve import ContinuousScheduler, ServeEngine
from tests.helpers import (
    expert_forms_on_record, pool_stat_keys_are, zero_cache)

EXACT = precision.Exact()


def tiny(**kw):
    kw.setdefault("dtype", jnp.float32)
    return GlmMoeDsaConfig.tiny(**kw)


def reference_config(cfg):
    """The configuration file's keys the reference reads, from the
    program's configuration object."""
    return dict(
        num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, rms_norm_eps=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta, index_n_heads=cfg.index_n_heads,
        index_head_dim=cfg.index_head_dim, index_topk=cfg.index_topk,
        index_norm_eps=cfg.index_norm_eps,
        indexer_types=list(cfg.indexer_types),
        mlp_layer_types=list(cfg.mlp_layer_types),
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        n_routed_experts=cfg.held, first_expert_held=cfg.first_expert,
        parameter_dtype=jnp.dtype(cfg.dtype).name)


@functools.cache
def drawn_params(cfg, seed=3):
    """Random parameters (norm scales round 1, offsets and a correction
    bias that move choices), in the type the module holds them in."""
    module = GlmMoeDsa(cfg)
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


@functools.cache
def _reference(cfg):
    def run(f32, tokens):
        masks = []
        logits = ref.logits(EXACT, reference_config(cfg), f32, tokens, masks)
        return logits, masks

    return jax.jit(run)


def reference_run(cfg, params, tokens):
    """The reference's logits and each layer's selection mask (one
    compiled program a configuration and shape: run op by op it is most of
    this file's time)."""
    f32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    logits, masks = _reference(cfg)(f32, tokens)
    return np.asarray(logits), [np.asarray(m) for m in masks]


def selections_of(mutated, cfg):
    sown = mutated["intermediates"]
    return [sown[f"selection_{l}"][0] for l in range(cfg.num_hidden_layers)]


# -- the configuration ---------------------------------------------------------

def test_the_published_layer_lists_are_the_defaults():
    cfg = GlmMoeDsaConfig.published()
    assert cfg.indexer_types[:7] == (
        "full", "full", "full", "shared", "shared", "shared", "full")
    assert cfg.indexer_types[-4:] == ("full", "shared", "shared", "shared")
    assert cfg.n_full_layers == 3 + 18
    assert cfg.mlp_layer_types.count("dense") == 3 and cfg.n_moe_layers == 75
    assert (cfg.latent_width, cfg.pool_width) == (576, 640)


def test_the_chips_share_preset_is_the_benchmarks_configuration():
    from benchmark.harness import program, spec

    share = GlmMoeDsaConfig.v5e256_share()
    assert share.layer_kinds == ("dense_full", "sparse_shared",
                                 "sparse_shared", "sparse_shared",
                                 "sparse_full")
    assert (share.held, share.vocab_size, share.n_routed_experts) == (
        8, 19360, 256)
    whole = GlmMoeDsaConfig.published()
    assert share.layer_kinds == tuple(
        f"{m}_{i}" for m, i in zip(whole.mlp_layer_types[2:7],
                                   whole.indexer_types[2:7]))
    cell = spec.load_cell("serve.glm-5.2.longdoc-saturated")
    assert program.program_config(cell.config) == share


@pytest.mark.parametrize("bad,match", [
    (dict(experts_held=0), "experts_held"),
    (dict(experts_held=4, first_expert=5), "first_expert"),
    (dict(indexer_types=("shared",) + ("full",) * 4), "start with a 'full'"),
    (dict(indexer_types=("full",) * 4), "indexer_types must name 5"),
    (dict(mlp_layer_types=("dense", "moe", "moe", "moe", "moe")),
     "mlp_layer_types"),
    (dict(qk_rope_head_dim=15), "even"),
    (dict(index_head_dim=8), "rotated"),
    (dict(index_topk=0), "index_topk"),
])
def test_config_refuses_what_is_no_such_model(bad, match):
    with pytest.raises(ValueError, match=match):
        tiny(**bad)


# -- the full forward pass -----------------------------------------------------

@pytest.mark.parametrize("dtype,atol", [("float32", 3e-5), ("bfloat16", 0.25)])
def test_forward_matches_the_reference(dtype, atol):
    """70 positions against a selection of 24: most queries read a real
    selection.  In bfloat16 near ties at the selection's edge may flip; the
    tolerance is the rounding's, as in the GLM-4.7 family's test."""
    cfg = tiny(experts_held=4, first_expert=2, dtype=jnp.dtype(dtype))
    params = drawn_params(cfg)
    tokens = tokens_of(cfg, (2, 70))
    got = GlmMoeDsa(cfg).apply({"params": params}, tokens)
    assert got.dtype == jnp.float32
    want, _ = reference_run(cfg, params, tokens)
    np.testing.assert_allclose(np.asarray(got), want, atol=atol)


def test_index_scores_and_selected_sets_are_the_references():
    cfg = tiny()
    params = drawn_params(cfg)
    tokens = tokens_of(cfg, (2, 70), seed=4)
    _, mutated = GlmMoeDsa(cfg).apply(
        {"params": params}, tokens, mutable=["intermediates"])
    got = [np.asarray(m) for m in selections_of(mutated, cfg)]
    _, want = reference_run(cfg, params, tokens)
    for layer, (mine, theirs) in enumerate(zip(got, want)):
        assert mine.shape == theirs.shape == (2, 70, 70)
        # Equal but for positions whose scores tie within float32 rounding:
        # none here, and never more than a pair a query.
        assert (mine != theirs).sum(-1).max() <= 2, layer
        assert (mine.sum(-1) == np.minimum(np.arange(70) + 1, 24)).all()
    # The scores themselves, layer 0's indexer on the model's own inputs.
    p = params["layer_0"]
    x = params["embed"][tokens]
    xn = parts.rms_norm(x, p["input_norm"]["scale"], cfg.rms_norm_eps)
    rcfg = reference_config(cfg)
    positions = jnp.broadcast_to(jnp.arange(70)[None], (2, 70))
    sizes = parts.mla_sizes(cfg)
    cq = parts.mla_query_latent(cfg, sizes, p["attn"], xn)
    q_i, k_i, w = dsa.indexer_project(cfg, sizes, p["indexer"], xn, cq,
                                      positions)
    mine = np.asarray(dsa.index_scores(q_i, w, k_i))
    theirs = np.asarray(ref.index_scores(EXACT, rcfg, xn, cq, p["indexer"]))
    causal = np.tril(np.ones((70, 70), bool))
    np.testing.assert_allclose(mine[:, causal], theirs[:, causal], atol=1e-6)
    assert np.isneginf(theirs[:, ~causal]).all()


@pytest.mark.parametrize("k", [1, 7, 24, 63, 64, 200])
def test_select_mask_is_top_k_with_the_lower_position_first_on_a_tie(k):
    rng = np.random.default_rng(k)
    scores = rng.normal(size=(3, 5, 64)).astype(np.float32)
    scores[0, 0, :] = 0.25                       # every score ties
    scores[0, 1, 10:40] = scores[0, 1, 10]       # a run of ties at the edge
    scores[1, 2, 30:] = -np.inf                  # a short row
    scores[2, 3, ::2] = -0.0
    scores[2, 3, 1::2] = 0.0
    got = np.asarray(dsa.select_mask(jnp.asarray(scores), k))
    order = np.argsort(-scores, axis=-1, kind="stable")[..., :k]
    want = np.zeros(scores.shape, bool)
    np.put_along_axis(want, order, True, axis=-1)
    if k >= 64:
        assert got.all()
        return
    # -0.0 orders below 0.0 by its bits; numpy's sort calls them equal.
    same = np.ones((3, 5), bool)
    same[2, 3] = False
    assert (got == want)[same].all()
    assert (got.sum(-1) == k).all()


def test_a_context_under_index_topk_is_plain_causal_latent_attention():
    """Prefill and decode through the pools with a selection that is never
    in force (24 positions against 24) equal the same weights' with no
    selection at all (a selection wider than the table row)."""
    cfg, wide = tiny(), tiny(index_topk=4096)
    params = drawn_params(cfg)
    tokens = tokens_of(cfg, (2, 24), seed=2)
    want = np.asarray(jax.jit(lambda p, t: GlmMoeDsa(wide).apply(
        {"params": p}, t))(params, tokens))
    got = cached_logits(cfg, params, tokens, prompt=16, chunk=8,
                        block_size=8, total=96)
    np.testing.assert_allclose(got, want, atol=3e-5)
    plain, _ = reference_run(wide, params, tokens)
    np.testing.assert_allclose(got, plain, atol=3e-5)


# -- the two pools -------------------------------------------------------------

def paged_for(slots, total_len, block_size):
    blocks = total_len // block_size
    free = iter(np.random.default_rng(5).permutation(
        np.arange(1, slots * blocks + 1)))        # block 0 is the trash block
    tables = np.array([[next(free) for _ in range(blocks)]
                       for _ in range(slots)], np.int32)
    return (PagedKVConfig(block_size=block_size, num_blocks=slots * blocks + 1),
            jnp.asarray(tables))


def cached_logits(cfg, params, tokens, *, prompt, chunk, block_size, total,
                  selections=None):
    """``tokens`` (2, T) through the paged pools: the prompt ``chunk``
    positions a call into slots 2 and 0 of 3, then a position a call over
    every slot; the logits ``(2, T, V)``.  ``selections`` receives, a call,
    each layer's selection as positions: a mask ``(2, t, span)``."""
    module = GlmMoeDsa(cfg)
    slots, T = 3, tokens.shape[1]
    paged, tables = paged_for(slots, total, block_size)
    rows = jnp.asarray([2, 0], jnp.int32)
    every = jnp.arange(slots, dtype=jnp.int32)
    kw = dict(decode=True, paged=paged, block_tables=tables,
              mutable=["cache", "intermediates"])
    cache = zero_cache(module, jnp.zeros((slots, 1), jnp.int32), decode=True,
                       slot_ids=every, paged=paged, block_tables=tables)
    # One program a shape of call, as the engine has: a chunk's, a step's.
    chunk_call = jax.jit(lambda cache, toks: module.apply(
        {"params": params, "cache": cache}, toks, slot_ids=rows, **kw))
    step_call = jax.jit(lambda cache, toks: module.apply(
        {"params": params, "cache": cache}, toks, slot_ids=every,
        live=jnp.asarray([True, False, True]), **kw))
    where = np.zeros((paged.num_blocks, block_size), np.int64)
    where[np.asarray(tables)] = (np.arange(total).reshape(-1, block_size)
                                 [None])        # a cell's position in its row
    out = []
    for off in range(0, prompt, chunk):
        got, mutated = chunk_call(cache, tokens[:, off:off + chunk])
        cache = mutated["cache"]
        out.append(np.asarray(got))
        if selections is not None:
            selections.append([np.asarray(m)
                               for m in selections_of(mutated, cfg)])
    for t in range(prompt, T):
        step = jnp.zeros((slots, 1), jnp.int32).at[rows].set(
            tokens[:, t:t + 1])
        got, mutated = step_call(cache, step)
        cache = mutated["cache"]
        out.append(np.asarray(got)[np.asarray(rows)])
        if selections is not None:
            masks = []
            for blocks, offsets, valid in selections_of(mutated, cfg):
                mask = np.zeros((slots, 1, total), bool)
                at = where[np.asarray(blocks), np.asarray(offsets)]
                for b in range(slots):
                    mask[b, 0, at[b][np.asarray(valid)[b]]] = True
                masks.append(mask[np.asarray(rows)])
            selections.append(masks)
    assert cache["index_pool"].shape == (
        cfg.n_full_layers, paged.num_blocks, block_size, cfg.index_head_dim)
    assert cache["latent_pool"].shape == (
        cfg.num_hidden_layers, paged.num_blocks, block_size, cfg.pool_width)
    return np.concatenate(out, axis=1)


@pytest.mark.parametrize("block_size,chunk,walk", [
    (8, 16, 32), (16, 16, 32), (8, 48, 32), (16, 24, 1024)])
def test_chunked_prefill_then_decode_gives_the_reference_logits(
        block_size, chunk, walk, monkeypatch):
    """Logits, not tokens: a prompt of 48 into two slots at once, ``chunk``
    positions a call (each chunk selects over the index keys the chunks
    before it wrote), then 20 positions one at a time through both pools,
    against the reference's one full forward pass; the context is walked
    ``walk`` positions a step (several steps, or one)."""
    monkeypatch.setattr(paged_call, "CONTEXT_CHUNK", walk)
    cfg = tiny(experts_held=4, first_expert=4)
    params = drawn_params(cfg)
    tokens = tokens_of(cfg, (2, 68), seed=1)
    want, masks = reference_run(cfg, params, tokens)
    sets = []
    got = cached_logits(cfg, params, tokens, prompt=48, chunk=chunk,
                        block_size=block_size, total=96, selections=sets)
    np.testing.assert_allclose(got, want, atol=3e-5)
    # Every call read the reference's sets, on every layer.
    at = 0
    for call in sets:
        t = call[0].shape[1]
        for layer, mask in enumerate(call):
            theirs = masks[layer][:, at:at + t]
            assert (mask[..., :68] == theirs).all(), (at, layer)
            assert not mask[..., 68:].any()
        at += t
    assert at == 68


def test_shared_layers_read_the_full_layers_set_and_hold_no_index_keys():
    cfg = tiny()
    params = drawn_params(cfg)
    assert [("indexer" in params[f"layer_{l}"]) for l in range(5)] == [
        True, False, False, False, True]
    tokens = tokens_of(cfg, (1, 60), seed=6)
    _, mutated = GlmMoeDsa(cfg).apply(
        {"params": params}, tokens, mutable=["intermediates"])
    first, *shared, last = [np.asarray(m)
                            for m in selections_of(mutated, cfg)]
    for mask in shared:
        assert (mask == first).all()
    assert (last != first).any()        # layer 4 selects for itself
    # Through the pools: two layers of index keys, five of latents, and a
    # decode step's shared layers gather the full layer's cells.
    sets = []
    cached_logits(cfg, params, jnp.tile(tokens, (2, 1)), prompt=48, chunk=16,
                  block_size=8, total=96, selections=sets)
    for call in sets:
        for mask in call[1:4]:
            assert (mask == call[0]).all()


def test_the_cache_holds_the_latent_and_on_full_layers_an_index_key():
    cfg = tiny()
    paged, _ = paged_for(2, 32, 16)
    g = dsa.cache_geometry(cfg, paged)
    assert g["kind"] == "latent_indexed" and g["pools_per_layer"] == 1
    assert g["values_per_token_layer"] == 64 + 16
    assert g["pool_width"] == 128 and g["padding_values"] == 48
    assert (g["index_layers"], g["index_values_per_token_layer"]) == (2, 32)
    assert g["bytes_per_token"] == 5 * 128 * 4 + 2 * 32 * 4
    assert g["selected_positions"] == 24
    assert g["latent_pool_bytes"] == 5 * paged.num_blocks * 16 * 128 * 4
    assert g["index_pool_bytes"] == 2 * paged.num_blocks * 16 * 32 * 4
    assert g["pool_bytes"] == g["latent_pool_bytes"] + g["index_pool_bytes"]
    assert g["block_bytes"] * paged.num_blocks == g["pool_bytes"]
    # The cell's: 5 x 1,280 B of latent row and 2 x 256 B of index key.
    share = GlmMoeDsaConfig.v5e256_share()
    g = dsa.cache_geometry(share, PagedKVConfig(
        block_size=16, num_blocks=16 * 512 + 1))
    assert g["bytes_per_token_layer"] == 1280
    assert g["index_bytes_per_token_layer"] == 256
    assert g["bytes_per_token"] == 5 * 1280 + 2 * 256
    assert g["pool_bytes"] == (16 * 512 + 1) * 16 * 6912


# -- the expert layer's share --------------------------------------------------

def test_the_32_shares_add_up_to_the_uncut_layer():
    """32 chips' 8-of-256 shares: their routed parts, and the shared
    expert counted once, are the reference's uncut layer."""
    whole = tiny(n_routed_experts=256, num_experts_per_tok=8)
    p = drawn_params(whole)["layer_1"]
    x = jnp.asarray(np.random.default_rng(7).normal(size=(24, 64)), jnp.float32)
    shared = parts.gated_mlp(p["shared"], x, jnp.float32)
    total, assigned = shared, 0
    for chip in range(32):
        cfg = dataclasses.replace(whole, experts_held=8, first_expert=8 * chip)
        mine = dict(p, experts=jax.tree.map(
            lambda w: w[8 * chip:8 * chip + 8], p["experts"]))
        y, row = parts.expert_layer(cfg, mine, x)
        total = total + (y - shared)
        assigned += int(row[:8].sum())
    assert assigned == 8 * 24      # every choice fell on exactly one chip
    want = ref.expert_ffn(EXACT, reference_config(whole), x, p)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=3e-5)


# -- a wrong selection ---------------------------------------------------------

@pytest.mark.parametrize("path", ["uncached", "through_the_pools"])
def test_a_program_that_selects_the_newest_positions_fails_the_tolerance(
        path, monkeypatch):
    """The chip's control (``benchmark/tools/selection_control.py``: the
    newest ``index_topk`` positions in the indexer's place), here: it
    agrees with the reference while everything is read and leaves it by a
    hundred tolerances after."""
    from benchmark.tools import selection_control as control

    cfg = tiny()
    params = drawn_params(cfg)
    tokens = tokens_of(cfg, (2, 70), seed=8)
    want, _ = reference_run(cfg, params, tokens)
    tolerance = 3e-5

    def run():
        if path == "uncached":
            return np.asarray(GlmMoeDsa(cfg).apply({"params": params}, tokens))
        return cached_logits(cfg, params, tokens, prompt=48, chunk=16,
                             block_size=8, total=96)

    assert np.abs(run() - want).max() <= tolerance
    monkeypatch.setattr(dsa, "select_mask", control.newest_mask)
    monkeypatch.setattr(dsa, "select_top", control.newest_top)
    wrong = run()
    np.testing.assert_allclose(wrong[:, :24], want[:, :24], atol=tolerance)
    assert np.abs(wrong[:, 24:48] - want[:, 24:48]).max() > 100 * tolerance
    assert np.abs(wrong[:, 48:] - want[:, 48:]).max() > 100 * tolerance


# -- through the engine and the scheduler --------------------------------------

SERVED = tiny(experts_held=4, first_expert=2)


@pytest.fixture(scope="module")
def engine():
    eng = ServeEngine("glm_moe_dsa", config=SERVED)
    eng.install_params(eng.shard_params(drawn_params(SERVED)))
    yield eng
    eng.close()


def served_requests(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab_size, n, dtype=np.int32), new)
            for n, new in ((32, 9), (16, 12), (48, 5), (64, 10), (16, 7))]


_REFERENCE_LOGITS = {}      # a sequence's, computed once whoever served it


def _gap_to_reference_best(engine, prompt, answer):
    """At every answered position, how far the served token's logit lies
    under the best logit of the reference's full forward pass."""
    seq = np.concatenate([prompt, answer])[None, :-1]
    key = seq.tobytes()
    if key not in _REFERENCE_LOGITS:
        _REFERENCE_LOGITS[key] = reference_run(
            SERVED, engine.params, jnp.asarray(seq))[0]
    at = _REFERENCE_LOGITS[key][0, len(prompt) - 1:]
    return at.max(-1) - at[np.arange(len(answer)), answer]


@pytest.mark.parametrize("megastep,async_decode", [(1, False), (4, True)])
def test_scheduler_serves_the_reference_best_tokens(engine, megastep,
                                                    async_decode):
    """Greedy answers through both pools, prompts of 16 to 64 against a
    selection of 24, prefilled 16 positions a launch: every token the
    reference's own first choice at its position, however the launches are
    fused and dispatched."""
    requests = served_requests(SERVED)
    with ContinuousScheduler(
            engine, num_slots=4, max_total_len=96, cache_mode="paged",
            block_size=16, megastep=megastep, prefill_budget=16,
            async_decode=async_decode) as sched:
        futures = [sched.submit(p, max_new_tokens=n) for p, n in requests]
        answers = [np.asarray(f.result(timeout=300)) for f in futures]
        stats = sched.stats()
    assert stats["moe_layer_steps"] > 0
    for (prompt, new), answer in zip(requests, answers):
        assert len(answer) == new
        assert _gap_to_reference_best(engine, prompt, answer).max() <= 1e-4
    paths = engine.attention_paths()
    assert set(paths["slot_prefill"]) == {dsa.MASKED}
    assert set(paths["slot_megastep"]) == {dsa.SELECTED}
    assert engine.decode_attention_launches()[dsa.SELECTED] > 0


def test_stats_name_the_form_each_programs_expert_layers_took(engine):
    """Chunks of 16 tokens of 2-of-8 (the dense form) and 4 slots a decode
    step (the grouped form), through both pools."""
    prompt, new = served_requests(SERVED)[0]
    with ContinuousScheduler(
            engine, num_slots=4, max_total_len=96, cache_mode="paged",
            block_size=16, megastep=4, prefill_budget=16) as sched:
        sched.submit(prompt, max_new_tokens=new).result(timeout=300)
        expert_forms_on_record(sched, experts=SERVED.n_routed_experts,
                               chunk=16)


def test_scheduler_counts_what_a_decode_step_reads(engine):
    """One request alone, a prompt of 48 and 9 tokens: each decode launch
    holds 48 + generated positions and reads ``index_topk`` latent rows a
    layer; the blocks it holds are one of each pool."""
    gauge = default_registry().gauge(
        "dtt_serve_kv_blocks_held", labelnames=("kind",))
    prompt, new = served_requests(SERVED)[2][0], 9
    with ContinuousScheduler(
            engine, num_slots=2, max_total_len=96, cache_mode="paged",
            block_size=16, megastep=1, prefill_budget=16) as sched:
        future = sched.submit(prompt, max_new_tokens=new)
        future.result(timeout=300)
        stats = sched.stats()
        geometry = engine.cache_geometry(sched.paged)
    launches = new - 1                     # the first token is the prefill's
    assert stats["iterations"] == launches
    assert stats["decode_live_positions"] == pytest.approx(
        sum(48 + 1 + i for i in range(launches)) / launches)
    assert stats["decode_selected_positions"] == SERVED.index_topk
    assert stats["decode_selected_positions"] <= stats["decode_live_positions"]
    # Retired: nothing held; the pool's bytes are both pools'.
    assert stats["kv_blocks_held_latent"] == stats["kv_blocks_held_index"] == 0
    assert stats["kv_bytes_held"] == 0
    assert gauge.labels(kind="latent").value == 0
    assert gauge.labels(kind="index").value == 0
    assert stats["kv_hbm_bytes"] >= geometry["pool_bytes"]
    assert geometry["block_bytes"] == 16 * (5 * 128 + 2 * 32) * 4


def test_stats_hold_the_index_keys_keys_and_no_other_pools(engine):
    pool_stat_keys_are(engine, "index")


def test_blocks_held_are_one_of_each_pool(engine):
    gauge = default_registry().gauge(
        "dtt_serve_kv_blocks_held", labelnames=("kind",))
    prompt = served_requests(SERVED)[3][0]          # 64 positions: 4 blocks
    with ContinuousScheduler(
            engine, num_slots=2, max_total_len=96, cache_mode="paged",
            block_size=16, megastep=1, prefill_budget=16,
            start=False) as sched:
        sched._thread.start()
        done = []
        future = sched.submit(prompt, max_new_tokens=20,
                              on_token=lambda toks: done.append(
                                  sched.stats()))
        future.result(timeout=300)
    seen = [s for s in done if s["kv_blocks_held_latent"] > 0]
    assert seen
    for stats in seen:
        held = stats["kv_blocks_held_latent"]
        assert stats["kv_blocks_held_index"] == held
        assert stats["kv_bytes_held"] == held * 16 * (5 * 128 + 2 * 32) * 4
        assert stats["kv_bytes_held_index"] == held * 16 * 2 * 32 * 4
    assert gauge.labels(kind="latent").value == 0


def test_the_prefill_chunk_span_carries_its_context(engine):
    from distributed_tensorflow_tpu.obs.trace import default_tracer

    tracer = default_tracer()
    prompt = served_requests(SERVED)[2][0]          # 48 positions
    was = tracer.enabled
    tracer.enable()
    try:
        with ContinuousScheduler(
                engine, num_slots=2, max_total_len=96, cache_mode="paged",
                block_size=16, prefill_budget=16) as sched:
            sched.submit(prompt, max_new_tokens=2).result(timeout=300)
        chunks = [args for _, _, _, _, args in tracer.spans(
            name="dtt/serve/prefill_chunk")]
    finally:
        tracer.enabled = was
    assert [(c["offset"], c["chunk_tokens"], c["context_tokens"])
            for c in chunks[-3:]] == [(0, 16, 16), (16, 16, 32), (32, 16, 48)]


REFUSED = {
    "dense_cache": dict(cache_mode="dense"),
    "kv_dtype": dict(kv_dtype="int8"),
    "per_shard_kv": dict(per_shard_kv=True),
    "slo_scheduling": dict(slo_scheduling=True),
    "spec_k": dict(spec_k=2),
    "prefix_cache": dict(prefix_cache=True),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_scheduler_refuses_what_the_two_pools_cannot_serve(engine, feature):
    kwargs = dict(num_slots=2, max_total_len=64, cache_mode="paged",
                  block_size=16, start=False)
    kwargs.update(REFUSED[feature])
    reason = dsa.SERVE_REFUSALS[feature]
    with pytest.raises(ValueError) as refused:
        ContinuousScheduler(engine, **kwargs)
    assert feature in str(refused.value) and reason in str(refused.value)


def test_a_tensor_mesh_is_refused_with_its_reason(mesh_2d):
    with pytest.raises(ValueError, match="tensor"):
        ServeEngine("glm_moe_dsa", mesh=mesh_2d, config=SERVED)


def test_the_module_has_no_dense_row_cache():
    module = GlmMoeDsa(tiny())
    with pytest.raises(ValueError, match="paged only"):
        module.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32),
                    decode=True)
    paged = PagedKVConfig(block_size=16, num_blocks=5, kv_dtype="int8")
    with pytest.raises(ValueError, match="kv_dtype"):
        module.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32),
                    decode=True, slot_ids=jnp.zeros((1,), jnp.int32),
                    paged=paged, block_tables=jnp.zeros((1, 4), jnp.int32))


def test_engine_reports_the_cache_geometry(engine):
    paged = PagedKVConfig(block_size=16, num_blocks=9)
    geometry = engine.cache_geometry(paged)
    assert geometry == dsa.cache_geometry(SERVED, paged)
    assert geometry["kind"] == "latent_indexed"


# -- through serve.py's driver ---------------------------------------------------

def test_the_serve_driver_takes_the_family():
    from distributed_tensorflow_tpu.serve.driver import ServeArgs, run_serve

    out = run_serve(ServeArgs(
        model="glm_moe_dsa", continuous=True, cache_mode="paged",
        num_slots=4, steps=6, megastep=4, async_decode=True,
        prefill_budget=64))
    assert out["model"] == "glm_moe_dsa" and out["preset"] == "tiny"
    assert out["completed"] == 6 and out["compile_post_warmup"] == 0
    assert out["cache_mode"] == "paged" and out["tokens_generated"] > 0


def test_the_serve_driver_refuses_the_fixed_batch_path_with_the_reason():
    from distributed_tensorflow_tpu.serve.driver import ServeArgs, run_serve

    with pytest.raises(ValueError, match="--continuous --cache_mode=paged"):
        run_serve(ServeArgs(model="glm_moe_dsa", steps=2))
