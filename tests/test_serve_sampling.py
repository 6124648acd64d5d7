"""Per-request sampling: vectorized selector parity, one-program
heterogeneity, penalty/seed semantics, and the SamplingParams surface.

The load-bearing claims, in test order:

- the vectorized ``_select_next`` with a UNIFORM parameter vector and
  zero counts is BITWISE identical to the scalar ``_select_next_scalar``
  it replaced (same logits, same rng, same counter) — greedy and
  sampled;
- the surviving scalar-keyed fixed-batch program and the slot programs
  driven with the matching uniform vector produce counter-exact
  identical sampled streams at the same batch shape;
- a scheduler mixing arbitrary per-request configs compiles exactly ONE
  program per (family, paged) — heterogeneous traffic never recompiles;
- greedy requests inside a heterogeneous batch still match the
  fixed-batch reference token for token (the jnp.where greedy-row
  equivalence), composed with megastep, spec decode, paged + chunked
  prefill;
- penalty counts reset with the slot (never inherited by the next
  occupant) and per-request seeds reproduce a stream independent of
  batch composition, megastep K, and spec k.

Greedy decode is deterministic on CPU, so parity is exact array
equality, not tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.serve import ContinuousScheduler, ServeEngine
from distributed_tensorflow_tpu.serve import sampling as sampling_lib
from distributed_tensorflow_tpu.serve.engine import (
    _select_next,
    _select_next_scalar,
)
from distributed_tensorflow_tpu.serve.sampling import (
    GREEDY,
    MixAssigner,
    SamplingParams,
    parse_sampling_mix,
)
from tests.helpers import fixed_reference


@pytest.fixture(scope="module")
def gpt2_engine(request):
    mesh_dp = request.getfixturevalue("mesh_dp")
    eng = ServeEngine("gpt2", mesh=mesh_dp, preset="tiny")
    yield eng
    eng.close()


def _slot_program_keys(engine):
    """Slot-family compile-cache keys currently resident in the engine."""
    return [k for k in engine._generate_fns
            if isinstance(k, tuple) and isinstance(k[0], str)
            and k[0].startswith("slot_")]


# ---------------------------------------------------------------------------
# SamplingParams / mix-spec surface
# ---------------------------------------------------------------------------

class TestSamplingParams:
    def test_defaults_are_greedy_and_frozen(self):
        p = SamplingParams()
        assert p.greedy and p == GREEDY
        with pytest.raises(Exception):  # frozen dataclass
            p.temperature = 1.0
        # hashable: the scheduler dedups configs via a set
        assert len({SamplingParams(), SamplingParams(temperature=0.5)}) == 2

    @pytest.mark.parametrize("kw", [
        {"temperature": float("nan")},
        {"top_k": -1},
        {"top_p": 0.0},
        {"top_p": 1.5},
        {"presence_penalty": float("inf")},
        {"seed": -2},
        {"seed": 2 ** 31},
    ])
    def test_validate_rejects(self, kw):
        with pytest.raises(ValueError):
            SamplingParams(**kw).validate()

    def test_coerce_forms(self):
        assert sampling_lib.coerce(None) is GREEDY
        p = sampling_lib.coerce({"temperature": 0.8, "top_k": 4})
        assert p == SamplingParams(temperature=0.8, top_k=4)
        with pytest.raises(TypeError):
            sampling_lib.coerce(0.8)

    def test_pack_fills_greedy_rows_and_steps(self):
        vec = sampling_lib.pack(
            [None, SamplingParams(temperature=0.7, top_k=3, seed=9)],
            steps=[0, 5])
        assert vec["temperature"].tolist() == pytest.approx([0.0, 0.7])
        assert vec["top_k"].tolist() == [0, 3]
        assert vec["seed"].tolist() == [-1, 9]
        assert vec["step"].tolist() == [0, 5]


class TestSamplingMix:
    def test_parse_round_trips_the_smoke_mix(self):
        mix = parse_sampling_mix("greedy:0.5,t0.8k40:0.3,t1.0p0.9:0.2")
        assert [p for p, _ in mix] == [
            GREEDY,
            SamplingParams(temperature=0.8, top_k=40),
            SamplingParams(temperature=1.0, top_p=0.9),
        ]
        assert [w for _, w in mix] == pytest.approx([0.5, 0.3, 0.2])

    def test_parse_all_fields_and_default_weight(self):
        ((p, w),) = parse_sampling_mix("t0.9k8p0.95a0.5f0.25s7")
        assert p == SamplingParams(temperature=0.9, top_k=8, top_p=0.95,
                                   presence_penalty=0.5,
                                   frequency_penalty=0.25, seed=7)
        assert w == 1.0

    @pytest.mark.parametrize("bad", ["", "x1.0", "t", "greedy:0",
                                     "t0.8:-1", "t2.0p0.0"])
    def test_parse_rejects(self, bad):
        with pytest.raises(ValueError):
            parse_sampling_mix(bad)

    def test_assigner_is_deterministic_and_proportional(self):
        mix = parse_sampling_mix("greedy:0.5,t0.8k40:0.3,t1.0p0.9:0.2")
        first, second = MixAssigner(mix), MixAssigner(mix)
        a = [first.next() for _ in range(20)]
        b = [second.next() for _ in range(20)]
        assert a == b  # same spec + same index -> same config
        counts = {p: a.count(p) for p, _ in mix}
        assert counts[GREEDY] == 10
        assert counts[SamplingParams(temperature=0.8, top_k=40)] == 6
        assert counts[SamplingParams(temperature=1.0, top_p=0.9)] == 4


# ---------------------------------------------------------------------------
# Selector: uniform vector is BITWISE the scalar selector
# ---------------------------------------------------------------------------

class TestSelectorParity:
    @pytest.mark.parametrize("temperature,top_k", [
        (0.0, 0), (-1.0, 5), (0.8, 40), (1.0, 0), (0.7, 1), (1.3, 256),
    ])
    @pytest.mark.parametrize("counter", [0, 7])
    def test_uniform_vector_bitwise_equals_scalar(self, temperature, top_k,
                                                  counter):
        logits = jax.random.normal(jax.random.key(3), (8, 256)) * 4.0
        rng = jax.random.key(11)
        ref = _select_next_scalar(logits, rng, counter, temperature, top_k)
        vec = {k: jnp.asarray(v) for k, v in
               sampling_lib.uniform(8, temperature, top_k).items()}
        got = _select_next(logits, rng, counter, vec,
                           jnp.zeros((8, 256), jnp.int32))
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_greedy_rows_are_argmax_inside_sampled_batch(self):
        logits = jax.random.normal(jax.random.key(5), (4, 64)) * 3.0
        vec = {k: jnp.asarray(v) for k, v in sampling_lib.pack(
            [None, SamplingParams(temperature=1.1, top_k=7),
             None, SamplingParams(temperature=0.9)],
            steps=[0] * 4).items()}
        got = np.asarray(_select_next(logits, jax.random.key(0), 0, vec,
                                      jnp.zeros((4, 64), jnp.int32)))
        argmax = np.asarray(jnp.argmax(logits, axis=-1))
        np.testing.assert_array_equal(got[[0, 2]], argmax[[0, 2]])

    def test_top_p_tiny_nucleus_collapses_to_argmax(self):
        logits = jax.random.normal(jax.random.key(7), (8, 128)) * 5.0
        vec = {k: jnp.asarray(v) for k, v in sampling_lib.pack(
            [SamplingParams(temperature=1.0, top_p=1e-6)] * 8,
            steps=[0] * 8).items()}
        got = np.asarray(_select_next(logits, jax.random.key(1), 3, vec,
                                      jnp.zeros((8, 128), jnp.int32)))
        np.testing.assert_array_equal(
            got, np.asarray(jnp.argmax(logits, axis=-1)))

    def test_penalties_steer_greedy_argmax_off_counted_tokens(self):
        logits = jnp.zeros((2, 8)).at[:, 3].set(5.0).at[:, 1].set(4.0)
        counts = jnp.zeros((2, 8), jnp.int32).at[1, 3].set(2)
        vec = {k: jnp.asarray(v) for k, v in sampling_lib.pack(
            [SamplingParams(frequency_penalty=10.0)] * 2,
            steps=[0, 0]).items()}
        got = np.asarray(_select_next(logits, jax.random.key(0), 0, vec,
                                      counts))
        assert got[0] == 3          # uncounted row keeps its argmax
        assert got[1] == 1          # 2 * 10.0 pushes token 3 below 1

    def test_seeded_rows_ignore_shared_rng_and_counter(self):
        logits = jax.random.normal(jax.random.key(9), (4, 64))
        vec = {k: jnp.asarray(v) for k, v in sampling_lib.pack(
            [SamplingParams(temperature=1.0, seed=77)] * 4,
            steps=[0, 1, 2, 3]).items()}
        a = _select_next(logits, jax.random.key(0), 0, vec,
                         jnp.zeros((4, 64), jnp.int32))
        b = _select_next(logits, jax.random.key(42), 1234, vec,
                         jnp.zeros((4, 64), jnp.int32))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Engine: scalar-keyed program vs slot programs, counter-exact
# ---------------------------------------------------------------------------

class TestEngineParity:
    def test_sampled_slot_stream_counter_exact_vs_fixed_batch(
            self, gpt2_engine):
        """Same batch shape, same base rng, same counters: the slot
        prefill + per-step decode path with a uniform sampling vector
        reproduces the scalar-keyed fixed-batch ``generate`` stream
        bit for bit — the categorical draws see identical logits,
        identical keys."""
        vocab = gpt2_engine.module.cfg.vocab_size
        prompts = np.random.default_rng(0).integers(
            0, vocab, size=(8, 5), dtype=np.int32)
        key = jax.random.key(42)
        ref = gpt2_engine.generate(prompts, 6, temperature=0.9, top_k=8,
                                   rng=key)
        cache = gpt2_engine.init_slot_cache(8, 16)
        counts = gpt2_engine.init_slot_counts(8)
        samp = sampling_lib.uniform(8, 0.9, 8)
        tok, cache, counts = gpt2_engine.prefill_into_slots(
            cache, prompts, np.arange(8), sampling=samp, counts=counts,
            rng=key, counter=0)
        streams = [np.asarray(jax.device_get(tok))]
        active = np.ones((8,), bool)
        for i in range(1, 6):
            tok, cache, counts = gpt2_engine.decode_slots(
                cache, streams[-1].reshape(8, 1), active, sampling=samp,
                counts=counts, rng=key, counter=i)
            streams.append(np.asarray(jax.device_get(tok)))
        np.testing.assert_array_equal(ref, np.stack(streams, axis=1))

    def test_legacy_scalar_kwargs_equal_explicit_uniform_vector(
            self, gpt2_engine):
        """The legacy arity (scalar temperature/top_k, no counts) is the
        SAME program fed a synthesized uniform vector — streams match
        the explicit-vector call exactly."""
        vocab = gpt2_engine.module.cfg.vocab_size
        prompt = np.random.default_rng(1).integers(
            0, vocab, size=(6,), dtype=np.int32)
        key = jax.random.key(5)

        def drive_legacy():
            cache = gpt2_engine.init_slot_cache(8, 16)
            tok, cache = gpt2_engine.prefill_into_slots(
                cache, prompt[None, :], [2], temperature=0.9, top_k=4,
                rng=key, counter=0)
            out = [int(np.asarray(jax.device_get(tok))[0])]
            active = np.zeros((8,), bool)
            active[2] = True
            last = np.zeros((8, 1), np.int32)
            for i in range(1, 4):
                last[2, 0] = out[-1]
                tok, cache = gpt2_engine.decode_slots(
                    cache, last, active, temperature=0.9, top_k=4,
                    rng=key, counter=i)
                out.append(int(np.asarray(jax.device_get(tok))[2]))
            return out

        def drive_vector():
            cache = gpt2_engine.init_slot_cache(8, 16)
            counts = gpt2_engine.init_slot_counts(8)
            tok, cache, counts = gpt2_engine.prefill_into_slots(
                cache, prompt[None, :], [2],
                sampling=sampling_lib.uniform(1, 0.9, 4), counts=counts,
                rng=key, counter=0)
            out = [int(np.asarray(jax.device_get(tok))[0])]
            active = np.zeros((8,), bool)
            active[2] = True
            last = np.zeros((8, 1), np.int32)
            for i in range(1, 4):
                last[2, 0] = out[-1]
                tok, cache, counts = gpt2_engine.decode_slots(
                    cache, last, active,
                    sampling=sampling_lib.uniform(8, 0.9, 4), counts=counts,
                    rng=key, counter=i)
                out.append(int(np.asarray(jax.device_get(tok))[2]))
            return out

        assert drive_legacy() == drive_vector()

    def test_greedy_scalar_keys_dedup_to_one_program(self, gpt2_engine):
        """Satellite bugfix: every greedy (temperature <= 0) scalar
        config is ONE fixed-batch program, not one per value pair."""
        assert ServeEngine.canonical_scalar_key(-1.0, 5) == (0.0, 0)
        assert ServeEngine.canonical_scalar_key(0.0, 0) == (0.0, 0)
        assert ServeEngine.canonical_scalar_key(0.9, -3) == (0.9, 0)
        a = gpt2_engine._decode_step_fn(-1.0, 5)
        b = gpt2_engine._decode_step_fn(0.0, 0)
        c = gpt2_engine._decode_step_fn(-0.5, 99)
        assert a is b is c
        greedy_keys = [k for k in gpt2_engine._generate_fns if k == "step"]
        assert len(greedy_keys) == 1

    def test_prefill_resets_previous_occupants_counts(self, gpt2_engine):
        """Penalty-count reset on admission: a slot's count row starts
        from zero for its new request — exactly one count (the first
        generated token) after prefill, whatever the previous occupant
        accumulated."""
        vocab = gpt2_engine.module.cfg.vocab_size
        prompt = np.arange(5, dtype=np.int32) % vocab
        cache = gpt2_engine.init_slot_cache(8, 16)
        counts = gpt2_engine.init_slot_counts(8)
        stale = jnp.asarray(counts).at[3].set(7)  # previous occupant
        tok, cache, counts = gpt2_engine.prefill_into_slots(
            cache, prompt[None, :], [3],
            sampling=sampling_lib.pack([GREEDY], [0]), counts=stale)
        row = np.asarray(jax.device_get(counts))[3]
        t = int(np.asarray(jax.device_get(tok))[0])
        assert row.sum() == 1 and row[t] == 1


# ---------------------------------------------------------------------------
# Scheduler: one program set under heterogeneous traffic + invariants
# ---------------------------------------------------------------------------

class TestHeterogeneousScheduler:
    CONFIGS = [
        None,                                             # scheduler default
        {"temperature": 0.8, "top_k": 40},
        {"temperature": 1.0, "top_p": 0.9},
        {"temperature": 1.2, "top_k": 3, "seed": 11},
        {"temperature": 0.7, "presence_penalty": 0.5},
        {"temperature": 0.9, "frequency_penalty": 0.25, "seed": 5},
    ]

    def test_mixed_configs_share_one_program_set(self, gpt2_engine):
        """THE tentpole claim: N distinct sampling configs in one batch
        compile exactly one slot_prefill and one decode program (the
        K=1 megastep, the scheduler's only plain-decode launch), and a
        second wave of fresh configs compiles NOTHING."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, vocab, size=(4 + i % 3,), dtype=np.int32)
                   for i in range(12)]
        with ContinuousScheduler(gpt2_engine, num_slots=8,
                                 max_total_len=24) as sched:
            futs = [sched.submit(p, max_new_tokens=3,
                                 sampling=self.CONFIGS[i % len(self.CONFIGS)])
                    for i, p in enumerate(prompts)]
            for f in futs:
                f.result(timeout=300)
            total_after_wave1 = gpt2_engine.compile_stats()["compile_total"]
            futs = [sched.submit(p, max_new_tokens=3,
                                 sampling={"temperature": 1.5 + 0.01 * i,
                                           "top_k": 2 + i})
                    for i, p in enumerate(prompts)]
            for f in futs:
                f.result(timeout=300)
            stats = sched.stats()
        keys = _slot_program_keys(gpt2_engine)
        assert keys.count(("slot_prefill", None)) == 1
        assert keys.count(("slot_megastep", 1, None)) == 1
        assert (gpt2_engine.compile_stats()["compile_total"]
                == total_after_wave1)
        assert stats["programs_cached"] >= 2
        assert stats["compile_total"] == total_after_wave1

    def test_greedy_rows_match_reference_inside_mixed_batch(
            self, gpt2_engine):
        """Greedy-row equivalence: a greedy request batched WITH sampled
        neighbours still reproduces the fixed-batch reference stream."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(4)
        greedy_reqs = [(rng.integers(0, vocab, size=(n,), dtype=np.int32), m)
                       for n, m in ((4, 5), (6, 3), (5, 7))]
        with ContinuousScheduler(gpt2_engine, num_slots=8,
                                 max_total_len=24) as sched:
            futs = [sched.submit(p, max_new_tokens=m)
                    for p, m in greedy_reqs]
            noise = [sched.submit(
                rng.integers(0, vocab, size=(5,), dtype=np.int32),
                max_new_tokens=6,
                sampling={"temperature": 1.3, "top_k": 4, "seed": i})
                for i in range(4)]
            outs = [f.result(timeout=300) for f in futs]
            for f in noise:
                f.result(timeout=300)
        for (p, m), out in zip(greedy_reqs, outs):
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, p, m))

    @pytest.mark.parametrize("sched_kw", [
        {"megastep": 4},
        {"spec_k": 4},
        {"prefill_budget": 3},
    ])
    def test_greedy_row_equivalence_composes(self, gpt2_engine, sched_kw):
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(6)
        p = rng.integers(0, vocab, size=(6,), dtype=np.int32)
        ref = fixed_reference(gpt2_engine, p, 6)
        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=24,
                                 **sched_kw) as sched:
            fut = sched.submit(p, max_new_tokens=6)
            noise = [sched.submit(
                rng.integers(0, vocab, size=(4,), dtype=np.int32),
                max_new_tokens=5,
                sampling={"temperature": 1.1, "top_p": 0.8, "seed": i})
                for i in range(3)]
            out = fut.result(timeout=300)
            for f in noise:
                f.result(timeout=300)
        np.testing.assert_array_equal(out, ref)

    def test_paged_mixed_batch_greedy_parity(self, gpt2_engine):
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(8)
        p = rng.integers(0, vocab, size=(6,), dtype=np.int32)
        ref = fixed_reference(gpt2_engine, p, 5)
        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=24,
                                 cache_mode="paged", block_size=4,
                                 prefill_budget=4) as sched:
            fut = sched.submit(p, max_new_tokens=5)
            noise = [sched.submit(
                rng.integers(0, vocab, size=(5,), dtype=np.int32),
                max_new_tokens=4,
                sampling={"temperature": 0.9, "top_k": 6})
                for _ in range(3)]
            out = fut.result(timeout=300)
            for f in noise:
                f.result(timeout=300)
        np.testing.assert_array_equal(out, ref)
        keys = _slot_program_keys(gpt2_engine)
        paged_decode = [k for k in keys if k[:2] == ("slot_megastep", 1)
                        and k[2] is not None]
        assert len(paged_decode) == 1

    def test_seeded_stream_reproduces_across_everything(self, gpt2_engine):
        """Seed-per-slot reproducibility: a seeded request's stream
        depends only on (seed, params, its own tokens) — not on batch
        neighbours, megastep K, or spec k."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(10)
        p = rng.integers(0, vocab, size=(5,), dtype=np.int32)
        cfg = {"temperature": 0.9, "top_k": 8, "seed": 123}

        def run(extra=0, **sched_kw):
            with ContinuousScheduler(gpt2_engine, num_slots=8,
                                     max_total_len=24, **sched_kw) as s:
                fut = s.submit(p, max_new_tokens=6, sampling=cfg)
                noise = [s.submit(
                    rng.integers(0, vocab, size=(4,), dtype=np.int32),
                    max_new_tokens=4,
                    sampling={"temperature": 1.2, "top_k": 3})
                    for _ in range(extra)]
                out = fut.result(timeout=300)
                for f in noise:
                    f.result(timeout=300)
            return out

        alone = run()
        np.testing.assert_array_equal(alone, run(extra=5))
        np.testing.assert_array_equal(alone, run(extra=3, megastep=4))
        np.testing.assert_array_equal(alone, run(spec_k=4))

    def test_frequency_penalty_forbids_repeats(self, gpt2_engine):
        """An overwhelming frequency penalty makes every emitted token
        distinct — the counts the penalty reads really do track THIS
        request's emissions."""
        vocab = gpt2_engine.module.cfg.vocab_size
        p = np.arange(7, dtype=np.int32) % vocab
        with ContinuousScheduler(gpt2_engine, num_slots=8,
                                 max_total_len=24) as sched:
            out = sched.submit(
                p, max_new_tokens=8,
                sampling={"frequency_penalty": 1e4}).result(timeout=300)
        assert len(set(out.tolist())) == len(out)

    def test_submit_validates_sampling(self, gpt2_engine):
        with ContinuousScheduler(gpt2_engine, num_slots=8,
                                 max_total_len=16) as sched:
            with pytest.raises(ValueError, match="top_p"):
                sched.submit(np.arange(4, dtype=np.int32),
                             max_new_tokens=2, sampling={"top_p": 0.0})
            with pytest.raises(TypeError, match="sampling"):
                sched.submit(np.arange(4, dtype=np.int32),
                             max_new_tokens=2, sampling=0.8)

    def test_stats_surface_counts_distinct_configs(self, gpt2_engine):
        with ContinuousScheduler(gpt2_engine, num_slots=8,
                                 max_total_len=16) as sched:
            stats = sched.stats()
            assert {"sampling_configs_active", "programs_cached",
                    "compile_total"} <= set(stats)
