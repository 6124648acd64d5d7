"""Speculative-decoding tests: n-gram prompt-lookup drafting feeding a
single-step (num_slots, k+1) verify forward must change ONLY dispatch
granularity, never content — greedy output is bit-identical spec on vs
off (the standing parity oracle), and sampled output is token-identical
for a single stream because the verify path burns (and refunds) exactly
the per-token RNG counters the sequential loop would.

Parity runs on BOTH acceptance meshes (pure data-parallel and
data=4 x tensor=2), in dense AND paged cache modes, over mixed
repetitive + random traffic (repetitive prompts make drafts land, random
ones exercise rejection).  Composition tests pin the invariants against
chunked prefill, the prefix cache, the megastep, and hot weight reload.
Draft-less iterations must fall through to the plain step without ever
building a k=0 verify program."""

import time

import numpy as np
import pytest

from distributed_tensorflow_tpu.serve import ContinuousScheduler, ServeEngine
from tests.helpers import fixed_reference


def _spec_requests(vocab, seed=3):
    """Mixed traffic: even requests tile a 4-token motif (the repetitive
    workload prompt lookup wins on — tiny greedy models loop on it, so
    drafts keep landing), odd requests are i.i.d. random (drafts mostly
    reject).  Horizons straddle spec_k=4 boundaries."""
    rng = np.random.default_rng(seed)
    motif = rng.integers(0, vocab, size=(4,), dtype=np.int32)
    reqs = []
    for i, (length, horizon) in enumerate(
            ((16, 12), (9, 6), (12, 8), (6, 5), (20, 10), (8, 3))):
        if i % 2 == 0:
            prompt = np.tile(motif, -(-length // 4))[:length]
        else:
            prompt = rng.integers(0, vocab, size=(length,), dtype=np.int32)
        reqs.append((prompt, horizon))
    return reqs


def _spec_off(engine, max_total_len, **kwargs):
    """The spec-off scheduler with rows as long as the spec-on one's (a
    verify launch may write ``spec_k`` positions past a row's end, so its
    rows are ``max_total_len + spec_k`` long): the two caches have one
    shape, and the prefill and decode programs of the first run serve the
    second."""
    return ContinuousScheduler(engine, max_total_len=max_total_len + 4,
                               **kwargs)


def _run_all(sched, reqs):
    futs = [sched.submit(p, max_new_tokens=m) for p, m in reqs]
    return [f.result(timeout=300) for f in futs]


@pytest.fixture(scope="module")
def gpt2_engine(request):
    mesh_dp = request.getfixturevalue("mesh_dp")
    eng = ServeEngine("gpt2", mesh=mesh_dp, preset="tiny")
    yield eng
    eng.close()


class TestCtorValidation:
    @pytest.mark.parametrize("bad_k", [0, -1])
    def test_zero_or_negative_spec_k_rejected(self, gpt2_engine, bad_k):
        """spec_k=0 must be expressed as spec_k=None (off), never as a
        degenerate always-empty verify configuration."""
        with pytest.raises(ValueError, match="spec_k"):
            ContinuousScheduler(gpt2_engine, spec_k=bad_k, start=False)

    def test_zero_spec_ngram_rejected(self, gpt2_engine):
        with pytest.raises(ValueError, match="spec_ngram"):
            ContinuousScheduler(gpt2_engine, spec_k=4, spec_ngram=0,
                                start=False)

    def test_stats_export_spec(self, gpt2_engine):
        sched = ContinuousScheduler(gpt2_engine, num_slots=8,
                                    max_total_len=32, spec_k=4,
                                    start=False)
        stats = sched.stats()
        assert stats["spec_k"] == 4.0
        for key in ("spec_launches", "spec_drafted", "spec_accepted",
                    "spec_emitted", "spec_acceptance_rate",
                    "spec_tokens_per_launch"):
            assert stats[key] == 0.0
        sched.close(timeout=0.1)


class TestSpecParity:
    """Greedy output must be bit-identical spec on vs off: the verifier
    samples the SAME per-position greedy targets the sequential loop
    would, so every kept token — accepted draft or correction — is
    exactly the sequential token."""

    @pytest.mark.parametrize("cache_mode", ["dense", "paged"])
    def test_spec_on_off_token_identical(self, gpt2_engine, cache_mode):
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _spec_requests(vocab)
        kwargs = dict(num_slots=8, max_total_len=64)
        if cache_mode == "paged":
            kwargs.update(cache_mode="paged", block_size=4)
        with _spec_off(gpt2_engine, **kwargs) as sched:
            baseline = _run_all(sched, reqs)
        with ContinuousScheduler(gpt2_engine, spec_k=4, **kwargs) as sched:
            spec = _run_all(sched, reqs)
            stats = sched.stats()
            assert stats["spec_k"] == 4.0
            assert stats["spec_launches"] > 0
            # The repetitive prompts make the drafter land: accepted
            # drafts mean fewer launches than decoded tokens (the
            # steps-per-token win the subsystem exists for).
            assert stats["spec_acceptance_rate"] > 0
            assert 0 < stats["megastep_launches"] \
                < stats["megastep_tokens"]
        for (prompt, horizon), base, out in zip(reqs, baseline, spec):
            np.testing.assert_array_equal(out, base)
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, horizon))

    @pytest.mark.parametrize("cache_mode", ["dense", "paged"])
    def test_parity_on_2d_mesh(self, mesh_2d, cache_mode):
        """data=4 x tensor=2: the (num_slots, k+1) verify forward's
        collectives and paged scatter must compose with sharded params
        and the tensor-sharded resident cache."""
        with ServeEngine("gpt2", mesh=mesh_2d, preset="tiny") as eng:
            vocab = eng.module.cfg.vocab_size
            reqs = _spec_requests(vocab, seed=5)
            kwargs = dict(num_slots=8, max_total_len=64)
            if cache_mode == "paged":
                kwargs.update(cache_mode="paged", block_size=4)
            with _spec_off(eng, **kwargs) as sched:
                baseline = _run_all(sched, reqs)
            with ContinuousScheduler(eng, spec_k=4, **kwargs) as sched:
                spec = _run_all(sched, reqs)
            for base, out in zip(baseline, spec):
                np.testing.assert_array_equal(out, base)


class TestSpecSampled:
    def test_sampled_stream_identical_spec_on_off(self, gpt2_engine):
        """Distribution-exactness made exact: the verify program samples
        position j's target with fold_in counter ``base + j`` — the very
        counters the sequential loop would burn — and refunds the
        unconsumed tail after a single-launch iteration.  A lone sampled
        stream is therefore TOKEN-identical spec on vs off at temp > 0,
        a far sharper oracle than any statistical test."""
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _spec_requests(vocab, seed=11)

        def run_sequential(**kw):
            # One request in flight at a time: multi-slot iterations
            # advance slots by different amounts, which no global counter
            # scheme can align with the sequential loop — single-stream
            # is where exact equality is promised.
            outs = []
            make = ContinuousScheduler if kw else _spec_off
            with make(gpt2_engine, num_slots=8, max_total_len=64,
                      temperature=0.8, top_k=20, **kw) as sched:
                for p, m in reqs:
                    outs.append(
                        sched.submit(p, max_new_tokens=m).result(timeout=300))
            return outs

        base = run_sequential()
        spec = run_sequential(spec_k=4)
        for i, (b, o) in enumerate(zip(base, spec)):
            np.testing.assert_array_equal(
                o, b, err_msg=f"sampled stream {i} diverged spec on/off")


class TestSpecEmptyDraft:
    def test_horizon_one_never_builds_verify_program(self, gpt2_engine):
        """Requests whose horizon leaves no draft room (max_new_tokens=1:
        the bonus token IS the whole stream) must ride the plain decode
        path — no verify launch, no ("slot_verify", ...) program built,
        spec counters untouched."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(7)
        motif = rng.integers(0, vocab, size=(4,), dtype=np.int32)
        before = {k for k in gpt2_engine._generate_fns
                  if k[0] == "slot_verify"}
        with ContinuousScheduler(gpt2_engine, num_slots=8,
                                 max_total_len=32, spec_k=4) as sched:
            baseline_ref = fixed_reference(gpt2_engine, np.tile(motif, 4), 1)
            out = sched.submit(np.tile(motif, 4),
                               max_new_tokens=1).result(timeout=300)
            stats = sched.stats()
        after = {k for k in gpt2_engine._generate_fns
                 if k[0] == "slot_verify"}
        assert after == before  # the k=0 guard never compiled a verify
        assert stats["spec_launches"] == 0
        assert stats["spec_drafted"] == 0
        np.testing.assert_array_equal(out, baseline_ref)


class TestSpecComposition:
    def test_chunked_prefill_composes(self, gpt2_engine):
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _spec_requests(vocab, seed=7)
        kwargs = dict(num_slots=8, max_total_len=64)
        with _spec_off(gpt2_engine, **kwargs) as sched:
            baseline = _run_all(sched, reqs)
        with ContinuousScheduler(gpt2_engine, spec_k=4, prefill_budget=4,
                                 **kwargs) as sched:
            stacked = _run_all(sched, reqs)
            stats = sched.stats()
            assert stats["prefill_chunks"] > len(reqs)
            assert stats["spec_launches"] > 0
        for base, out in zip(baseline, stacked):
            np.testing.assert_array_equal(out, base)

    def test_prefix_cache_composes(self, gpt2_engine):
        """Prefix-mapped blocks skip prefill, then verify launches append
        behind them through the same block tables — hits and output must
        match the spec-off paged run."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(13)
        motif = rng.integers(0, vocab, size=(4,), dtype=np.int32)
        prefix = np.tile(motif, 2)
        reqs = [(np.concatenate([prefix, np.tile(motif, -(-n // 4))[:n]]),
                 6) for n in (4, 6, 9)]
        kwargs = dict(num_slots=8, max_total_len=64, cache_mode="paged",
                      block_size=4, prefix_cache=True)
        runs = []
        for spec_k in (None, 4):
            make = ContinuousScheduler if spec_k else _spec_off
            with make(gpt2_engine, spec_k=spec_k, **kwargs) as sched:
                outs = [sched.submit(p, max_new_tokens=m).result(timeout=300)
                        for p, m in reqs]
                stats = sched.stats()
                runs.append((outs, stats["prefill_tokens_skipped"],
                             stats["prefix_hits"]))
        (base_outs, base_skip, base_hits), (outs, skip, hits) = runs
        assert skip == base_skip > 0
        assert hits == base_hits > 0
        for base, out in zip(base_outs, outs):
            np.testing.assert_array_equal(out, base)

    def test_megastep_composes(self, gpt2_engine):
        """spec_k + megastep: drafting iterations go through the verify
        launch, draft-less ones through the K-step fused program — both
        pure dispatch changes, so stacking stays bit-identical."""
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _spec_requests(vocab, seed=9)
        kwargs = dict(num_slots=8, max_total_len=64)
        with _spec_off(gpt2_engine, **kwargs) as sched:
            baseline = _run_all(sched, reqs)
        with ContinuousScheduler(gpt2_engine, spec_k=4, megastep=4,
                                 **kwargs) as sched:
            stacked = _run_all(sched, reqs)
            assert sched.stats()["spec_launches"] > 0
        for base, out in zip(baseline, stacked):
            np.testing.assert_array_equal(out, base)

    def test_hot_reload_composes(self, gpt2_engine):
        """Weights staged mid-request swap in at an iteration boundary;
        the in-flight request keeps decoding (and verifying) on its
        admission generation — spec output stays bit-identical to the
        fixed-batch reference across the swap."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(21)
        motif = rng.integers(0, vocab, size=(4,), dtype=np.int32)
        whale = np.tile(motif, 16)
        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=96,
                                 prefill_budget=2, spec_k=4) as sched:
            gen0 = sched.generation
            fut = sched.submit(whale, max_new_tokens=8)
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                s = sched.stats()
                if s["prefilling_slots"] >= 1.0 and s["prefill_chunks"] >= 1:
                    break
                time.sleep(0.001)
            else:
                pytest.fail("whale never observed mid-prefill")
            sched.update_params(gpt2_engine.params, generation=gen0 + 3)
            out = fut.result(timeout=300)
            assert fut.generation == gen0
            post = sched.submit(whale[:8], max_new_tokens=6)
            post.result(timeout=300)
            assert post.generation == gen0 + 3
        np.testing.assert_array_equal(
            out, fixed_reference(gpt2_engine, whale, 8))
