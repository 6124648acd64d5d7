"""Chunked-prefill tests: the per-iteration prefill budget must be a pure
SCHEDULING change — the same K/V lands at the same cache positions chunk
by chunk, so greedy output is bit-identical budget on vs off — while the
interleaving it buys is real: short requests admitted next to a whale
prompt start decoding (and retire) while the whale is still prefilling.

Parity runs on BOTH acceptance meshes (pure data-parallel and
data=4 x tensor=2) and in dense AND paged cache modes; composition tests
pin the invariants against the prefix cache (cached tokens cost zero
budget, ``prefill_tokens_skipped`` unchanged by chunking) and hot weight
reload (a request mid-prefill finishes on its admission generation).
"""

import time

import numpy as np
import pytest

from distributed_tensorflow_tpu.serve import ContinuousScheduler, ServeEngine
from tests.helpers import fixed_reference


def _mixed_requests(vocab, seed=3):
    """Mixed traffic around a budget of 4: even multiples (4, 8), ragged
    tails (6 -> 4+2, 9 -> 4+4+1), and a 17-token whale (4 chunks + ragged
    last)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i, length in enumerate((4, 6, 9, 8, 17, 5)):
        horizon = (2, 5, 3, 4)[i % 4]
        reqs.append((rng.integers(0, vocab, size=(length,), dtype=np.int32),
                     horizon))
    return reqs


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
    def test_negative_budget_rejected(self, gpt2_engine):
        with pytest.raises(ValueError, match="prefill_budget"):
            ContinuousScheduler(gpt2_engine, prefill_budget=-1, start=False)

    def test_stats_export_budget(self, gpt2_engine):
        sched = ContinuousScheduler(gpt2_engine, num_slots=8,
                                    max_total_len=32, prefill_budget=4,
                                    start=False)
        stats = sched.stats()
        assert stats["prefill_budget"] == 4.0
        assert stats["prefill_chunks"] == 0.0
        assert stats["prefilling_slots"] == 0.0
        assert stats["prefill_backlog_tokens"] == 0.0
        sched.close(timeout=0.1)


class TestChunkedParity:
    """Greedy output must be bit-identical budget on vs off: chunking
    changes WHEN prompt tokens prefill, never what K/V they write."""

    @pytest.mark.parametrize("cache_mode", ["dense", "paged"])
    def test_budget_on_off_token_identical(self, gpt2_engine, cache_mode):
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab)
        kwargs = dict(num_slots=8, max_total_len=32)
        if cache_mode == "paged":
            kwargs.update(cache_mode="paged", block_size=4)
        with ContinuousScheduler(gpt2_engine, **kwargs) as sched:
            baseline = _run_all(sched, reqs)
            assert sched.stats()["prefill_chunks"] == len(reqs)  # one-shot
        with ContinuousScheduler(gpt2_engine, prefill_budget=4,
                                 **kwargs) as sched:
            chunked = _run_all(sched, reqs)
            assert sched.stats()["prefill_chunks"] > len(reqs)
        for (prompt, horizon), base, out in zip(reqs, baseline, chunked):
            np.testing.assert_array_equal(out, base)
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, horizon))

    def test_parity_on_2d_mesh(self, mesh_2d):
        """data=4 x tensor=2: chunk offsets must compose with sharded
        params and the tensor-sharded resident cache."""
        with ServeEngine("gpt2", mesh=mesh_2d, preset="tiny") as eng:
            vocab = eng.module.cfg.vocab_size
            reqs = _mixed_requests(vocab, seed=5)
            with ContinuousScheduler(eng, num_slots=8,
                                     max_total_len=32) as sched:
                baseline = _run_all(sched, reqs)
            with ContinuousScheduler(eng, num_slots=8, max_total_len=32,
                                     prefill_budget=4) as sched:
                chunked = _run_all(sched, reqs)
            for base, out in zip(baseline, chunked):
                np.testing.assert_array_equal(out, base)

    def test_ragged_last_chunk(self, gpt2_engine):
        """A prompt that is not a multiple of the budget ends on a ragged
        chunk: 10 = 4 + 4 + 2."""
        vocab = gpt2_engine.module.cfg.vocab_size
        prompt = (np.arange(10, dtype=np.int32) * 7) % vocab
        ref = fixed_reference(gpt2_engine, prompt, 4)
        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=32,
                                 prefill_budget=4) as sched:
            out = sched.submit(prompt, max_new_tokens=4).result(timeout=300)
            assert sched.stats()["prefill_chunks"] == 3
        np.testing.assert_array_equal(out, ref)


class TestChunkedScheduling:
    def test_shorts_retire_while_whale_prefills(self, gpt2_engine):
        """The interleaving claim: shorts admitted next to a whale decode
        to completion while the whale is still prefilling.  The done
        callback runs on the loop thread the moment a short's future
        resolves — the whale's slot must still be mid-prefill there."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(11)
        whale = rng.integers(0, vocab, size=(64,), dtype=np.int32)
        shorts = [rng.integers(0, vocab, size=(4,), dtype=np.int32)
                  for _ in range(2)]
        sched = ContinuousScheduler(gpt2_engine, num_slots=8,
                                    max_total_len=96, prefill_budget=8,
                                    start=False)
        prefilling_at_retire = []

        def record(_fut):
            prefilling_at_retire.append(
                sched.stats()["prefilling_slots"])

        try:
            whale_fut = sched.submit(whale, max_new_tokens=2)
            short_futs = [sched.submit(s, max_new_tokens=2) for s in shorts]
            for f in short_futs:
                f.add_done_callback(record)
            sched._thread.start()
            whale_ref = fixed_reference(gpt2_engine, whale, 2)
            short_refs = [fixed_reference(gpt2_engine, s, 2)
                          for s in shorts]
            np.testing.assert_array_equal(
                whale_fut.result(timeout=300), whale_ref)
            for f, ref in zip(short_futs, short_refs):
                np.testing.assert_array_equal(f.result(timeout=300), ref)
        finally:
            sched.close()
        # Both shorts retired while the whale (64 tokens / budget 8 = 8
        # chunk iterations) was still prefilling.
        assert prefilling_at_retire == [1.0, 1.0]

    def test_block_reservation_once_at_admit(self, gpt2_engine):
        """Paged mode reserves the worst-case block count ONCE, at admit —
        chunking must not re-reserve per chunk or change the per-request
        block footprint.  The pool drains back to empty either way."""
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab, seed=7)
        kwargs = dict(num_slots=8, max_total_len=32, cache_mode="paged",
                      block_size=4)
        hists = []
        for budget in (0, 4):
            with ContinuousScheduler(gpt2_engine, prefill_budget=budget,
                                     **kwargs) as sched:
                _run_all(sched, reqs)
                stats = sched.stats()
                assert stats["blocks_in_use"] == 0.0  # all freed at retire
                hists.append(sched.blocks_per_request_hist())
        # Per-request block footprints are a function of prompt + horizon
        # alone — chunking must not change what any request pinned.
        assert hists[0] == hists[1]


class TestChunkedReload:
    def test_mid_prefill_finishes_on_admission_generation(self, gpt2_engine):
        """A weight generation staged while a chunked request is mid-
        prefill must NOT split the request across generations: every
        remaining chunk (and its decode) runs on the params pinned at
        admission."""
        vocab = gpt2_engine.module.cfg.vocab_size
        whale = (np.arange(64, dtype=np.int32) * 3) % vocab
        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=96,
                                 prefill_budget=2) as sched:
            gen0 = sched.generation
            fut = sched.submit(whale, max_new_tokens=2)
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                s = sched.stats()
                if s["prefilling_slots"] >= 1.0 and s["prefill_chunks"] >= 1:
                    break
                time.sleep(0.001)
            else:
                pytest.fail("whale never observed mid-prefill")
            # Same avals, new tag: the generation bookkeeping is what is
            # under test, not the weights themselves.
            sched.update_params(gpt2_engine.params, generation=gen0 + 7)
            out = fut.result(timeout=300)
            assert fut.generation == gen0
            post = sched.submit(whale[:4], max_new_tokens=2)
            post.result(timeout=300)
            assert post.generation == gen0 + 7
            assert sched.generation == gen0 + 7
        np.testing.assert_array_equal(
            out, fixed_reference(gpt2_engine, whale, 2))


class TestChunkedPrefix:
    def test_prefix_skip_unchanged_by_chunking(self, gpt2_engine):
        """Cached-prefix tokens cost ZERO budget: the chunk walk starts
        past the mapped blocks, so what the cache skips — and the greedy
        output — is identical budget on vs off."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(13)
        prefix = rng.integers(0, vocab, size=(8,), dtype=np.int32)
        reqs = [(np.concatenate([prefix, rng.integers(
                     0, vocab, size=(n,), dtype=np.int32)]), 3)
                for n in (4, 6, 9)]
        kwargs = dict(num_slots=8, max_total_len=32, cache_mode="paged",
                      block_size=4, prefix_cache=True)
        runs = []
        for budget in (0, 4):
            with ContinuousScheduler(gpt2_engine, prefill_budget=budget,
                                     **kwargs) as sched:
                # Sequential submits: request N's prefix blocks are
                # registered before N+1 maps them, both runs identically.
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
