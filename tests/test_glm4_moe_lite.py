"""The latent-attention, sparse-expert decoder family (``models/
glm4_moe_lite.py``) at a tiny size on the CPU, against the benchmark's plain
reference (``benchmark/reference/glm4_moe_lite.py``: full forward pass, no
cache, float32).

What is held here: prefill then decode through the paged latent cache gives
the reference's logits (module calls) and the reference's choice of tokens
through the scheduler (megastep 1 and 4, asynchronous dispatch on and off,
two block sizes); the absorbed and the expanded latent attention agree; the
router is float32 whatever the compute type; the shares of the expert layer
add up to the uncut layer; no token is dropped; every scheduler feature the
family cannot serve is refused at construction with its reason; the device's
counts of the router's choices equal a hand count.
"""

import dataclasses

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import glm4_moe_lite as ref
from benchmark.reference import precision
from distributed_tensorflow_tpu.models import decoder_parts as parts
from distributed_tensorflow_tpu.models import glm4_moe_lite as glm
from distributed_tensorflow_tpu.models.glm4_moe_lite import (
    Glm4MoeLite, Glm4MoeLiteConfig)
from distributed_tensorflow_tpu.models.gpt2 import PagedKVConfig
from distributed_tensorflow_tpu.obs.metrics import default_registry
from distributed_tensorflow_tpu.ops import grouped_matmul
from distributed_tensorflow_tpu.serve import ContinuousScheduler, ServeEngine
from tests.helpers import (
    expert_forms_on_record, pool_stat_keys_are, zero_cache)

EXACT = precision.Exact()


def tiny(**kw):
    kw.setdefault("dtype", jnp.float32)
    return Glm4MoeLiteConfig.tiny(**kw)


def reference_config(cfg):
    """The configuration file's keys the reference reads, from the
    program's configuration object."""
    return dict(
        num_attention_heads=cfg.num_attention_heads,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        kv_lora_rank=cfg.kv_lora_rank, rms_norm_eps=cfg.rms_norm_eps,
        rope_theta=cfg.rope_theta,
        num_experts_per_tok=cfg.num_experts_per_tok,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        n_routed_experts=cfg.held, first_expert_held=cfg.first_expert,
        parameter_dtype=jnp.dtype(cfg.dtype).name)


@functools.cache
def drawn_params(cfg, seed=3):
    """Random parameters (norm scales round 1, a correction bias that moves
    choices), in the type the module holds them in."""
    module = Glm4MoeLite(cfg)
    abstract = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    keys = iter(jax.random.split(jax.random.key(seed), 200))

    def one(path, leaf):
        name = path[-1].key
        noise = jax.random.normal(next(keys), leaf.shape, jnp.float32)
        value = {"scale": 1.0 + 0.1 * noise, "bias": 0.05 * noise}.get(
            name, 0.05 * noise)
        return value.astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(one, abstract)


@functools.cache
def reference_logits(cfg):
    """The reference's full forward pass as one program a configuration and
    shape: run op by op it compiled its layer scans anew at every call."""
    return jax.jit(lambda params, tokens: ref.logits(
        EXACT, reference_config(cfg), params, tokens))


def tokens_of(cfg, shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, shape), jnp.int32)


# -- the full forward pass -----------------------------------------------------

@pytest.mark.parametrize("dtype,atol", [("float32", 2e-5), ("bfloat16", 0.25)])
def test_forward_matches_the_reference(dtype, atol):
    cfg = tiny(experts_held=4, first_expert=2, num_hidden_layers=4,
               first_k_dense_replace=2, dtype=jnp.dtype(dtype))
    params = drawn_params(cfg)
    tokens = tokens_of(cfg, (3, 40))
    got = jax.jit(lambda p, t: Glm4MoeLite(cfg).apply({"params": p}, t))(
        params, tokens)
    assert got.dtype == jnp.float32
    f32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    want = reference_logits(cfg)(f32, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)


@pytest.mark.parametrize("bad,match", [
    (dict(experts_held=0), "experts_held"),
    (dict(experts_held=9), "experts_held"),
    (dict(experts_held=4, first_expert=5), "first_expert"),
    (dict(first_k_dense_replace=0), "first_k_dense_replace"),
    (dict(qk_rope_head_dim=15), "even"),
])
def test_config_refuses_a_share_that_is_no_share(bad, match):
    with pytest.raises(ValueError, match=match):
        tiny(**bad)


def test_rope_scores_depend_on_the_distance_alone():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 1, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 16)), jnp.float32)

    def score(pq, pk):
        at = lambda p: jnp.full((1, 1), p, jnp.int32)
        return float(jnp.sum(parts.rope(q, at(pq), 1e6) * parts.rope(k, at(pk), 1e6)))

    assert score(7, 3) == pytest.approx(score(104, 100), abs=1e-5)
    assert score(7, 3) != pytest.approx(score(7, 4), abs=1e-3)


# -- latent attention ----------------------------------------------------------

@pytest.mark.parametrize("queries", [1, 5])
def test_absorbed_and_expanded_attention_agree(queries):
    cfg = tiny()
    p = drawn_params(cfg)["dense_layers"]["attn"]
    p = jax.tree.map(lambda x: x[0], p)
    rng = np.random.default_rng(2)
    B, S, H = 2, 24, cfg.num_attention_heads
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q_n, q_r = draw(B, queries, H, cfg.qk_nope_head_dim), draw(
        B, queries, H, cfg.qk_rope_head_dim)
    latent, k_r = draw(B, S, cfg.kv_lora_rank), draw(B, S, cfg.qk_rope_head_dim)
    mask = jnp.arange(S)[None, None, :] <= (S - queries + jnp.arange(queries)
                                            )[None, :, None]
    mask = jnp.broadcast_to(mask, (B, queries, S))
    sizes = parts.mla_sizes(cfg)
    absorbed = parts.mla_attend(cfg, sizes, p, q_n, q_r, latent, k_r, mask,
                                True)
    expanded = parts.mla_attend(cfg, sizes, p, q_n, q_r, latent, k_r, mask,
                                False)
    assert absorbed.shape == (B, queries, H * cfg.v_head_dim)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=2e-5)


def paged_for(cfg, slots, total_len, block_size):
    blocks = total_len // block_size
    tables = np.full((slots, blocks), -1, np.int32)
    free = iter(np.random.default_rng(5).permutation(
        np.arange(1, slots * blocks + 1)))        # block 0 is the trash block
    for s in range(slots):
        tables[s] = [next(free) for _ in range(blocks)]
    return (PagedKVConfig(block_size=block_size, num_blocks=slots * blocks + 1),
            jnp.asarray(tables))


@pytest.mark.parametrize("block_size", [8, 16])
def test_prefill_then_decode_gives_the_reference_logits(block_size):
    """Logits, not tokens: a prompt into two slots at once, then one
    position at a time through the cache, against the reference's one full
    forward pass over the whole sequence."""
    cfg = tiny(experts_held=4, first_expert=4)
    module, params = Glm4MoeLite(cfg), drawn_params(cfg)
    slots, prompt, steps = 3, 11, 9
    paged, tables = paged_for(cfg, slots, 32, block_size)
    tokens = tokens_of(cfg, (2, prompt + steps))
    want = np.asarray(reference_logits(cfg)(params, tokens))
    rows = jnp.asarray([2, 0], jnp.int32)        # slot 1 stays empty
    kw = dict(decode=True, paged=paged, block_tables=tables, mutable=["cache"])
    every = jnp.arange(slots, dtype=jnp.int32)
    cache = zero_cache(module, jnp.zeros((slots, 1), jnp.int32), decode=True,
                       slot_ids=every, paged=paged, block_tables=tables)
    # One program a shape of call, as the engine has: the prompt's, a step's.
    call = jax.jit(lambda cache, toks, ids: module.apply(
        {"params": params, "cache": cache}, toks, slot_ids=ids, **kw))
    got, out = call(cache, tokens[:, :prompt], rows)
    np.testing.assert_allclose(np.asarray(got), want[:, :prompt], atol=3e-5)
    cache = out["cache"]
    for t in range(prompt, prompt + steps):
        step = jnp.zeros((slots, 1), jnp.int32).at[rows].set(tokens[:, t:t + 1])
        got, out = call(cache, step, every)
        cache = out["cache"]
        np.testing.assert_allclose(np.asarray(got)[np.asarray(rows), 0],
                                   want[:, t], atol=3e-5)
    assert list(np.asarray(cache["cache_index"])) == [20, steps, 20]


def test_the_cache_holds_the_latent_and_one_rotary_key():
    cfg = tiny()
    paged, _ = paged_for(cfg, 2, 32, 16)
    geometry = glm.cache_geometry(cfg, paged)
    assert geometry["values_per_token_layer"] == 64 + 16
    assert geometry["pool_width"] == 128 and geometry["padding_values"] == 48
    assert geometry["pools_per_layer"] == 1
    assert geometry["pool_bytes"] == 3 * paged.num_blocks * 16 * 128 * 4
    full = Glm4MoeLiteConfig()
    assert (full.latent_width, full.pool_width) == (576, 640)


# -- the expert layer ----------------------------------------------------------

def one_expert_layer(cfg, seed=3):
    params = drawn_params(cfg, seed)["moe_layers"]
    return jax.tree.map(lambda x: x[0], params)


def test_router_is_float32_whatever_the_compute_type():
    """The program's router on bfloat16 activations gives the reference's
    float32 weights; the same router computed in bfloat16 does not, by ten
    times the tolerance."""
    cfg = tiny(dtype=jnp.bfloat16, n_routed_experts=64, num_experts_per_tok=4)
    p = one_expert_layer(cfg)["router"]
    x = jnp.asarray(np.random.default_rng(4).normal(size=(96, 64)),
                    jnp.bfloat16)
    chosen, weights = parts.route(cfg, p, x)
    assert weights.dtype == jnp.float32
    dense = np.zeros((96, 64), np.float32)
    np.put_along_axis(dense, np.asarray(chosen), np.asarray(weights), axis=1)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    want = np.asarray(ref._route(EXACT, reference_config(cfg), x.astype(
        jnp.float32), f32))
    tolerance = 1e-5
    np.testing.assert_allclose(dense, want, atol=tolerance)

    class Bf16Dot:
        def einsum(self, spec, a, b):
            return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(
                jnp.bfloat16)).astype(jnp.float32)

    low = np.asarray(ref._route(Bf16Dot(), reference_config(cfg),
                                x.astype(jnp.float32), f32))
    assert np.abs(low - want).max() > 10 * tolerance


def test_correction_bias_orders_the_choice_and_weighs_nothing():
    cfg = tiny()
    p = one_expert_layer(cfg)["router"]
    x = jnp.asarray(np.random.default_rng(6).normal(size=(32, 64)), jnp.float32)
    forced = dict(p, bias=jnp.zeros((8,)).at[jnp.asarray([1, 6])].set(10.0))
    chosen, weights = parts.route(cfg, forced, x)
    assert set(np.unique(np.asarray(chosen))) == {1, 6}
    np.testing.assert_allclose(np.asarray(weights.sum(-1)),
                               cfg.routed_scaling_factor, rtol=1e-6)
    scores = jax.nn.sigmoid(x @ p["kernel"])
    picked = np.take_along_axis(np.asarray(scores), np.asarray(chosen), axis=1)
    np.testing.assert_allclose(
        np.asarray(weights),
        picked / picked.sum(-1, keepdims=True) * cfg.routed_scaling_factor,
        rtol=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips' 8-of-64 shares: their routed parts, and the shared
    expert counted once, are the reference's uncut layer."""
    whole = tiny(n_routed_experts=64, num_experts_per_tok=4)
    p = one_expert_layer(whole)
    x = jnp.asarray(np.random.default_rng(7).normal(size=(40, 64)), jnp.float32)
    shared = parts.gated_mlp(p["shared"], x, jnp.float32)
    total, assigned = shared, 0
    for chip in range(8):
        cfg = dataclasses.replace(whole, experts_held=8, first_expert=8 * chip)
        mine = dict(p, experts=jax.tree.map(
            lambda w: w[8 * chip:8 * chip + 8], p["experts"]))
        y, row = parts.expert_layer(cfg, mine, x)
        total = total + (y - shared)
        assigned += int(row[:8].sum())
        assert int(row[:8].sum() + row[8 + parts.COUNT_ABSENT]) == 4 * 40
    assert assigned == 4 * 40      # every choice fell on exactly one chip
    want = ref.expert_ffn(EXACT, reference_config(whole), x, p)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-5)
    uncut, _ = parts.expert_layer(whole, p, x)
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(want), atol=2e-5)


def test_no_token_is_dropped_when_every_token_takes_one_expert():
    cfg = tiny(experts_held=4, first_expert=0)
    p = one_expert_layer(cfg)
    p["router"] = dict(p["router"], bias=jnp.zeros((8,)).at[
        jnp.asarray([2, 5])].set(10.0))    # expert 2 is held, 5 is not
    x = jnp.asarray(np.random.default_rng(8).normal(size=(200, 64)),
                    jnp.float32)
    y, row = parts.expert_layer(cfg, p, x)
    assert list(np.asarray(row)) == [0, 0, 200, 0, 200, 1, 1]
    want = ref.expert_ffn(EXACT, reference_config(cfg), x, p)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    _, masked = parts.expert_layer(cfg, p, x, live=jnp.arange(200) < 50)
    assert list(np.asarray(masked)) == [0, 0, 50, 0, 50, 1, 1]
    _, none = parts.expert_layer(cfg, p, x, live=jnp.zeros((200,), bool))
    assert not np.asarray(none).any()


# -- each assignment once, against every held expert over every token ---------

def every_expert_layer(cfg, p, x, live):
    """The plain form of ``expert_layer``, kept here as its reference: every
    held expert over every token in float32 at the highest precision,
    weighed by gates that are zero where the router chose otherwise; and
    the ``moe_counts`` row counted as the parent counted it."""
    exact = dict(precision=jax.lax.Precision.HIGHEST)
    wide = lambda a: a.astype(jnp.float32)
    chosen, weights = parts.route(cfg, p["router"], x)
    held = cfg.first_expert + jnp.arange(cfg.held, dtype=chosen.dtype)
    hit = chosen[:, :, None] == held[None, None, :]
    gates = jnp.sum(jnp.where(hit, weights[:, :, None], 0.0), axis=1)
    xd = x.astype(cfg.dtype)
    g, u = jnp.split(jnp.einsum(
        "nd,egd->eng", wide(xd), wide(p["experts"]["gate_up"]["kernel"]),
        **exact), 2, axis=-1)
    each = jnp.einsum(
        "enf,efd->end", wide((jax.nn.silu(g) * u).astype(cfg.dtype)),
        wide(p["experts"]["down"]["kernel"]), **exact)
    y = jnp.sum(gates.T[:, :, None] * each, axis=0)
    if "shared" in p:
        y = y + parts.gated_mlp(p["shared"], xd, cfg.dtype)
    counted = live.astype(jnp.int32)
    tokens = jnp.sum(hit.any(axis=1) * counted[:, None], axis=0)
    extra = [cfg.num_experts_per_tok * counted.sum() - tokens.sum(),
             (tokens > 0).sum(), counted.sum() > 0]
    return y, jnp.concatenate([tokens, jnp.stack(extra)]).astype(jnp.int32)


def router_family(name, **share):
    """A toy configuration with the family's router (``cfg.router``, its
    scale and its norm), eight experts, two a token."""
    if name == "mellum":
        from distributed_tensorflow_tpu.models.mellum import MellumConfig
        return MellumConfig.tiny(dtype=jnp.float32, **share)
    if name == "glm_moe_dsa":
        from distributed_tensorflow_tpu.models.glm_moe_dsa import (
            GlmMoeDsaConfig)
        return GlmMoeDsaConfig.tiny(dtype=jnp.float32, **share)
    return tiny(**share)


def toy_expert_layer(cfg, pinned, seed=11):
    """One layer's leaves at the toy widths (64 wide, experts of 32).  The
    first input feature is 1 for every token, so the router's first row adds
    ``pinned``'s (expert, logit) pairs to every token's logits."""
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(
        0.05 * rng.normal(size=shape), jnp.float32)
    kernel = np.array(draw(64, 8))
    kernel[0] = 0.0
    for expert, logit in pinned:
        kernel[0, expert] = logit
    p = {"router": {"kernel": jnp.asarray(kernel)},
         "experts": {"gate_up": {"kernel": draw(cfg.held, 64, 64)},
                     "down": {"kernel": draw(cfg.held, 32, 64)}}}
    if cfg.router == "sigmoid_bias":
        p["router"]["bias"] = draw(8)
        p["shared"] = {"gate_up": {"kernel": draw(64, 64)},
                       "down": {"kernel": draw(32, 64)}}
    return p


# (tokens, held experts, first held, (expert, logit) pairs, share alive)
GROUPED_CASES = {
    # Expert 3 takes every row: two row tiles of it, the second part full.
    "an_expert_given_every_row": (200, 4, 2, ((3, 40.0),), 1.0),
    "an_expert_given_none": (200, 4, 2, ((4, -40.0),), 1.0),
    # What a decode step of the sixth cell mostly sees.
    "no_row_on_any_held_expert": (
        200, 4, 2, tuple((e, -40.0) for e in (2, 3, 4, 5)), 1.0),
    "one_token": (1, 4, 2, (), 1.0),
    "tokens_not_a_multiple_of_the_row_tile": (40, 4, 2, (), 1.0),
    "a_live_mask": (200, 4, 2, (), 0.6),
    "no_token_alive": (200, 4, 2, (), 0.0),
    "every_expert_held": (200, 8, 0, (), 1.0),
}
_grouped_layers = {}


# A shape of its own is a trace of its own (a second each), so the cases
# that have one meet this family's router and the kernels; the others meet
# all three routers and both implementations (the serving tests of the
# three families run the plain one at their own shapes all day).
OWN_SHAPE = ("one_token", "tokens_not_a_multiple_of_the_row_tile",
             "every_expert_held")


@pytest.mark.parametrize("family,kernels,case", [
    (family, kernels, case)
    for family, kernels in (("glm4_moe_lite", False), ("mellum", False),
                            ("glm_moe_dsa", False), ("glm4_moe_lite", True))
    for case in GROUPED_CASES
    if kernels or case not in OWN_SHAPE])
def test_each_assignment_once_is_every_expert_over_every_token(
        family, kernels, case, monkeypatch):
    """The grouped form (``ops/grouped_matmul.py``: off the TPU by
    ``jax.lax.ragged_dot``, and with ``kernels`` by its two Pallas kernels
    in the interpreter) gives what every held expert over every token
    gives, for each family's router, and counts the same row."""
    n, held, first, pinned, alive = GROUPED_CASES[case]
    cfg = router_family(family, experts_held=held, first_expert=first)
    if kernels:
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
    # Whatever form a call of this shape would take in a served program.
    monkeypatch.setattr(parts, "expert_form",
                        lambda *shape: grouped_matmul.GROUPED)
    key = (family, kernels, n, held)
    if key not in _grouped_layers:      # one trace a shape, not one a case
        _grouped_layers[key] = jax.jit(lambda p, x, live: (
            parts.expert_layer(cfg, p, x, live),
            every_expert_layer(cfg, p, x, live)))
    p = toy_expert_layer(cfg, pinned)
    rng = np.random.default_rng(n)
    x = np.asarray(rng.normal(size=(n, 64)), np.float32)
    x[:, 0] = 1.0
    live = jnp.asarray(rng.random(n) < alive)
    with grouped_matmul.record_forms(forms := {}, "layer"):
        (y, row), (want, want_row) = _grouped_layers[key](
            p, jnp.asarray(x), live)
    if forms:                           # the call that traced
        tm = grouped_matmul.tile_rows(n)
        assert forms == {f"layer/{n}": (grouped_matmul.GROUPED, tm * min(
            held * -(-n // tm), n * min(2, held) // tm + held))}
    np.testing.assert_array_equal(np.asarray(row), np.asarray(want_row))
    counted = np.asarray(row)
    if case == "an_expert_given_every_row":
        assert counted[3 - first] == n
    if case == "an_expert_given_none":
        assert counted[4 - first] == 0 and counted[:held].sum() > 0
    if case in ("no_row_on_any_held_expert", "no_token_alive"):
        assert not counted[:held].any()
    mask = np.asarray(live)
    np.testing.assert_allclose(np.asarray(y)[mask], np.asarray(want)[mask],
                               atol=2e-5)
    # A token that does not count is given no row: its routed part is 0.
    shared = (np.asarray(parts.gated_mlp(p["shared"], jnp.asarray(x),
                                       jnp.float32))
              if "shared" in p else 0.0)
    np.testing.assert_allclose((np.asarray(y) - shared)[~mask], 0.0,
                               atol=2e-5)


# -- through the engine and the scheduler --------------------------------------

SERVED = tiny(experts_held=4, first_expert=2)


@pytest.fixture(scope="module")
def engine():
    eng = ServeEngine("glm4_moe_lite", config=SERVED)
    eng.install_params(eng.shard_params(drawn_params(SERVED)))
    yield eng
    eng.close()


def served_requests(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab_size, n, dtype=np.int32), new)
            for n, new in ((16, 9), (8, 12), (16, 5), (24, 10), (8, 7))]


def _gap_to_reference_best(engine, prompt, answer):
    """At every answered position, how far the served token's logit lies
    under the best logit of the reference's full forward pass."""
    seq = np.concatenate([prompt, answer])[None, :-1]
    logits = np.asarray(reference_logits(SERVED)(
        engine.params, jnp.asarray(seq)))
    at = logits[0, len(prompt) - 1:]
    return at.max(-1) - at[np.arange(len(answer)), answer]


@pytest.mark.parametrize("block_size", [8, 16])
@pytest.mark.parametrize("async_decode", [False, True])
@pytest.mark.parametrize("megastep", [1, 4])
def test_scheduler_serves_the_reference_best_tokens(engine, megastep,
                                                    async_decode, block_size):
    """Greedy answers through the paged latent cache, every one the
    reference's own first choice at its position (the logit gap is zero up
    to float32 rounding), however the launches are fused and dispatched."""
    requests = served_requests(SERVED)
    with ContinuousScheduler(
            engine, num_slots=4, max_total_len=64, cache_mode="paged",
            block_size=block_size, megastep=megastep,
            async_decode=async_decode) as sched:
        futures = [sched.submit(p, max_new_tokens=n) for p, n in requests]
        answers = [np.asarray(f.result(timeout=300)) for f in futures]
        stats = sched.stats()
    assert stats["moe_layer_steps"] > 0
    for (prompt, new), answer in zip(requests, answers):
        assert len(answer) == new
        assert _gap_to_reference_best(engine, prompt, answer).max() <= 1e-4
    paths = engine.attention_paths()
    assert set(paths["slot_prefill"]) == {glm.EXPANDED}
    assert set(paths["slot_megastep"]) == {glm.ABSORBED}
    assert engine.decode_attention_launches()[glm.ABSORBED] > 0


def test_stats_hold_no_pool_keys_for_one_pool(engine):
    pool_stat_keys_are(engine)


def test_chunked_prefill_writes_the_same_latents(engine):
    """A prompt prefilled 8 positions a launch (each chunk attends over
    the pool's rows the chunks before it wrote) is answered as one
    prefilled whole: the reference's first choices again."""
    requests = served_requests(SERVED)
    with ContinuousScheduler(
            engine, num_slots=4, max_total_len=64, cache_mode="paged",
            block_size=16, megastep=4, async_decode=True,
            prefill_budget=8) as sched:
        futures = [sched.submit(p, max_new_tokens=n) for p, n in requests]
        answers = [np.asarray(f.result(timeout=300)) for f in futures]
        assert sched.stats()["prefill_chunks"] == sum(
            len(p) // 8 for p, _ in requests)
    for (prompt, new), answer in zip(requests, answers):
        assert _gap_to_reference_best(engine, prompt, answer).max() <= 1e-4


def test_decode_counts_the_routers_choices(engine):
    """One request alone: every decode step of every expert layer makes
    ``num_experts_per_tok`` choices for one token, here or elsewhere."""
    counter = default_registry().counter(
        "dtt_serve_moe_assignments_total", labelnames=("held",))
    before = {h: counter.labels(held=h).value for h in ("here", "absent")}
    prompt, new = served_requests(SERVED)[0]
    with ContinuousScheduler(
            engine, num_slots=2, max_total_len=64, cache_mode="paged",
            block_size=16, megastep=4) as sched:
        answer = sched.submit(prompt, max_new_tokens=new).result(timeout=300)
        stats = sched.stats()
    steps = len(answer) - 1                # the first token is the prefill's
    layers, k = SERVED.n_moe_layers, SERVED.num_experts_per_tok
    assert stats["moe_experts_held"] == 4
    assert stats["moe_layer_steps"] == layers * steps
    assert (stats["moe_assignments_here"] + stats["moe_assignments_absent"]
            == k * layers * steps)
    assert 0 <= stats["moe_active_experts_per_step"] <= k
    assert stats["moe_load_max_over_mean"] >= 1.0
    assert stats["decode_live_positions"] > len(prompt)
    for held in ("here", "absent"):
        assert (counter.labels(held=held).value - before[held]
                == stats[f"moe_assignments_{held}"])
    # The hand count: the same tokens through the uncached forward pass.
    seq = jnp.asarray(np.concatenate([prompt, answer])[None, :-1])
    here = _choices_here(SERVED, engine.params, seq)[len(prompt):]
    assert stats["moe_assignments_here"] == here.sum()


def test_stats_name_the_form_each_programs_expert_layers_took(engine):
    """A prompt of 16 in one prefill launch (16 tokens of 2-of-8: every
    held expert is all but sure of a row, the dense form) and 2 slots a
    decode step (the grouped form)."""
    prompt, new = served_requests(SERVED)[0]
    with ContinuousScheduler(
            engine, num_slots=2, max_total_len=64, cache_mode="paged",
            block_size=16, megastep=4) as sched:
        sched.submit(prompt, max_new_tokens=new).result(timeout=300)
        expert_forms_on_record(sched, experts=SERVED.n_routed_experts,
                               chunk=len(prompt))
        assert set(sched.stats()["moe_expert_form"].values()) == {
            grouped_matmul.GROUPED, grouped_matmul.DENSE}


def _choices_here(cfg, params, tokens):
    """Per position of one row, the router's choices that fall on held
    experts, summed over the expert layers: the reference's own layers,
    one after the other, with the router asked beside each."""
    rcfg, eps = reference_config(cfg), cfg.rms_norm_eps
    lo, hi = cfg.first_expert, cfg.first_expert + cfg.held
    x = params["embed"][tokens]
    total = np.zeros((tokens.shape[1],), np.int64)
    for name, moe in (("dense_layers", False), ("moe_layers", True)):
        for i in range(params[name]["input_norm"]["scale"].shape[0]):
            p = jax.tree.map(lambda w: w[i], params[name])
            if moe:
                h = x + ref._attention(EXACT, rcfg, ref._rms_norm(
                    x, p["input_norm"], eps), p["attn"])
                weights = ref._route(EXACT, rcfg, ref._rms_norm(
                    h, p["post_norm"], eps)[0], p["router"])
                total += np.asarray(weights[:, lo:hi] > 0).sum(-1)
            x = ref._layer(EXACT, rcfg, x, p, moe)
    return total


REFUSED = {
    "dense_cache": dict(cache_mode="dense"),
    "kv_dtype": dict(kv_dtype="int8"),
    "per_shard_kv": dict(per_shard_kv=True),
    "slo_scheduling": dict(slo_scheduling=True),
    "spec_k": dict(spec_k=2),
    "prefix_cache": dict(prefix_cache=True),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_scheduler_refuses_what_the_latent_pool_cannot_serve(engine, feature):
    kwargs = dict(num_slots=2, max_total_len=64, cache_mode="paged",
                  block_size=16, start=False)
    kwargs.update(REFUSED[feature])
    reason = glm.SERVE_REFUSALS[feature]
    with pytest.raises(ValueError) as refused:
        ContinuousScheduler(engine, **kwargs)
    assert feature in str(refused.value) and reason in str(refused.value)


def test_a_tensor_mesh_is_refused_with_its_reason(mesh_2d):
    with pytest.raises(ValueError, match="tensor"):
        ServeEngine("glm4_moe_lite", mesh=mesh_2d, config=SERVED)


def test_the_module_has_no_dense_row_cache():
    cfg = tiny()
    module = Glm4MoeLite(cfg)
    with pytest.raises(ValueError, match="paged only"):
        module.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32),
                    decode=True)
    paged = PagedKVConfig(block_size=16, num_blocks=5, kv_dtype="int8")
    with pytest.raises(ValueError, match="kv_dtype"):
        module.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32),
                    decode=True, slot_ids=jnp.zeros((1,), jnp.int32),
                    paged=paged, block_tables=jnp.zeros((1, 4), jnp.int32))


def test_gpt2_declares_its_cache_and_refuses_nothing(mesh_dp):
    from distributed_tensorflow_tpu.models import get_workload

    workload = get_workload("gpt2", preset="tiny", mesh=mesh_dp)
    assert workload.serve_refusals == {}
    paged = PagedKVConfig(block_size=16, num_blocks=9)
    geometry = workload.cache_geometry(paged)
    assert geometry["kind"] == "key_value" and geometry["pools_per_layer"] == 2
    assert geometry["padding_values"] == 0
    assert workload.cache_rules is not None


def test_engine_reports_the_cache_geometry(engine):
    paged = PagedKVConfig(block_size=16, num_blocks=9)
    geometry = engine.cache_geometry(paged)
    assert geometry == glm.cache_geometry(SERVED, paged)
    assert geometry["kind"] == "latent"


# -- through serve.py's driver ---------------------------------------------------

def test_the_serve_driver_takes_the_family():
    from distributed_tensorflow_tpu.serve.driver import ServeArgs, run_serve

    out = run_serve(ServeArgs(
        model="glm4_moe_lite", continuous=True, cache_mode="paged",
        num_slots=4, steps=6, megastep=4, async_decode=True))
    assert out["model"] == "glm4_moe_lite" and out["preset"] == "tiny"
    assert out["completed"] == 6 and out["compile_post_warmup"] == 0
    assert out["cache_mode"] == "paged" and out["tokens_generated"] > 0


def test_the_serve_driver_refuses_the_fixed_batch_path_with_the_reason():
    from distributed_tensorflow_tpu.serve.driver import ServeArgs, run_serve

    with pytest.raises(ValueError, match="--continuous --cache_mode=paged"):
        run_serve(ServeArgs(model="glm4_moe_lite", steps=2))


def test_the_chips_share_preset_is_the_benchmarks_configuration():
    share = Glm4MoeLiteConfig.v5e8_share()
    assert (share.num_hidden_layers, share.held, share.vocab_size,
            share.n_routed_experts) == (21, 8, 19360, 64)
    whole = Glm4MoeLiteConfig.flash()
    assert (whole.num_hidden_layers, whole.held, whole.vocab_size) == (
        47, 64, 154880)
    assert dataclasses.replace(
        share, num_hidden_layers=47, vocab_size=154880,
        experts_held=None) == whole
