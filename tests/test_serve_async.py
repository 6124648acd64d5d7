"""Deep async decode tests: dispatching up to ``async_depth`` megasteps
ahead of the oldest unfetched launch must be a pure SCHEDULING change —
greedy output is bit-identical async on vs off at every depth, dense and
paged, on both acceptance meshes, composed with megastep, chunked
prefill, the prefix cache, speculative decoding and mid-stream hot
reload — while the semantics it does change are pinned explicitly: a
request submitted while a launch ring is in flight sees its first
decoded tokens only after the ring wraps (admission lag buys the
overlap), and launches resolve strictly in dispatch order off the
dedicated fetch thread.

``--megastep=auto`` rides the same loop: the autotuner picks K from the
observed dispatch-vs-step-time ratio and FREEZES, so compiled-program
identity stays stable; the control law is pinned against a stubbed
timing source (no real clocks in the assert path).

The ctor-validation and stubbed-autotune tests never launch a decode
program; everything that compiles end-to-end decode carries
``serve_slow`` (a selection marker — tier-1 excludes only ``slow``).

``DTT_ASYNC_DEPTH`` overrides the ring depth the async schedulers here
run at (default 2 — the classic double buffer); ``scripts/t1.sh``'s
opt-in ``DTT_SERVE_ASYNC=1`` pass reruns the serve_slow suites at
depth 4.  ``async_decode=False`` is the same ring at depth 1, which
``TestDepthOneIsSynchronous`` pins."""

import os

import numpy as np
import pytest

from distributed_tensorflow_tpu.serve import ContinuousScheduler, ServeEngine
from tests.helpers import fixed_reference

# Ring depth for the async schedulers under test.  2 is today's double
# buffer; the t1.sh DTT_SERVE_ASYNC pass exports 4 so every parity and
# composition claim is re-proven with three launches in flight.
_DEPTH = int(os.environ.get("DTT_ASYNC_DEPTH", "2"))


def _mixed_requests(vocab, seed=3):
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
    def test_bogus_megastep_string_rejected(self, gpt2_engine):
        with pytest.raises(ValueError, match="megastep"):
            ContinuousScheduler(gpt2_engine, megastep="fast", start=False)

    def test_auto_megastep_with_spec_rejected(self, gpt2_engine):
        with pytest.raises(ValueError, match="auto"):
            ContinuousScheduler(gpt2_engine, megastep="auto", spec_k=2,
                                start=False)

    def test_bad_async_depth_rejected(self, gpt2_engine):
        with pytest.raises(ValueError, match="async_depth"):
            ContinuousScheduler(gpt2_engine, async_decode=True,
                                async_depth=0, start=False)

    def test_stats_export_async_keys(self, gpt2_engine):
        sched = ContinuousScheduler(gpt2_engine, num_slots=8,
                                    max_total_len=32, megastep="auto",
                                    async_decode=True, async_depth=4,
                                    start=False)
        stats = sched.stats()
        assert stats["async_decode"] == 1.0
        assert stats["megastep_auto"] == 1.0
        assert stats["megastep_autotune_frozen"] == 0.0
        assert stats["megastep"] == 1.0  # autotune starts at the classic K
        assert stats["device_clock"] == 0.0
        assert stats["device_idle_fraction"] == 0.0
        assert stats["async_depth"] == 4.0
        assert stats["async_sync_fallbacks"] == 0.0
        assert stats["async_ring_depth_avg"] == 0.0
        assert stats["async_ring_depth_max"] == 0.0
        assert stats["async_fetch_wait_s"] == 0.0
        # The fetch thread is lazy: nothing dispatched, nothing started.
        assert sched._fetch_thread is None
        sched.close(timeout=0.1)


@pytest.mark.serve_slow
class TestAsyncParity:
    """Greedy output must be bit-identical async on vs off: the double
    buffer changes WHEN tokens land on host, never what any row
    decodes."""

    # One K per cache mode keeps the compile surface affordable while
    # covering both regimes: K=3 forces carry chains across launches
    # (ragged vs every horizon), K=8 swallows whole horizons in one
    # launch — both must survive an extra launch always in flight.
    @pytest.mark.parametrize("cache_mode,steps", [("dense", 3),
                                                  ("paged", 8)])
    def test_async_on_off_token_identical(self, gpt2_engine, cache_mode,
                                          steps):
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab)
        kwargs = dict(num_slots=8, max_total_len=32)
        if cache_mode == "paged":
            kwargs.update(cache_mode="paged", block_size=4)
        with ContinuousScheduler(gpt2_engine, **kwargs) as sched:
            baseline = _run_all(sched, reqs)
        with ContinuousScheduler(gpt2_engine, megastep=steps,
                                 async_decode=True, async_depth=_DEPTH,
                                 **kwargs) as sched:
            overlapped = _run_all(sched, reqs)
            stats = sched.stats()
            assert stats["async_decode"] == 1.0
            assert stats["megastep_launches"] > 0
            assert stats["async_sync_fallbacks"] == 0.0
        for (prompt, horizon), base, out in zip(reqs, baseline,
                                                overlapped):
            np.testing.assert_array_equal(out, base)
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, horizon))

    def test_parity_on_2d_mesh(self, mesh_2d):
        """data=4 x tensor=2, paged (the harder case: device-resident
        block tables ride the in-flight launch): the sharded outputs
        chain into the next dispatch without a host round-trip."""
        with ServeEngine("gpt2", mesh=mesh_2d, preset="tiny") as eng:
            vocab = eng.module.cfg.vocab_size
            reqs = _mixed_requests(vocab, seed=5)
            kwargs = dict(num_slots=8, max_total_len=32,
                          cache_mode="paged", block_size=4)
            with ContinuousScheduler(eng, **kwargs) as sched:
                baseline = _run_all(sched, reqs)
            with ContinuousScheduler(eng, megastep=4, async_decode=True,
                                     async_depth=_DEPTH, **kwargs) as sched:
                overlapped = _run_all(sched, reqs)
            for base, out in zip(baseline, overlapped):
                np.testing.assert_array_equal(out, base)


@pytest.mark.serve_slow
class TestDepthOneIsSynchronous:
    """``async_decode=False`` IS the launch ring at depth 1: the same
    dispatch/resolve halves, the same launches, iteration for iteration.
    Stepped by hand (``start=False``, every request queued first) so the
    launch counts do not depend on thread timing."""

    @pytest.mark.parametrize("steps", [1, 4])
    @pytest.mark.parametrize("cache_mode", ["dense", "paged"])
    def test_async_off_equals_ring_of_depth_one(self, gpt2_engine,
                                                cache_mode, steps):
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab, seed=17)
        kwargs = dict(num_slots=8, max_total_len=32, megastep=steps)
        if cache_mode == "paged":
            kwargs.update(cache_mode="paged", block_size=4)
        runs = []
        for ring in (dict(async_decode=False),
                     dict(async_decode=True, async_depth=1)):
            sched = ContinuousScheduler(gpt2_engine, start=False,
                                        **kwargs, **ring)
            try:
                futs = [sched.submit(p, max_new_tokens=m) for p, m in reqs]
                n = 0
                while not all(f.done() for f in futs) and n < 200:
                    sched._iteration()
                    assert not sched._ring  # depth 1 leaves nothing behind
                    n += 1
                outs = [np.asarray(f.result(timeout=60)) for f in futs]
                stats = sched.stats()
            finally:
                sched.close(timeout=5.0)
            assert stats["async_ring_depth_max"] == 1.0
            assert stats["async_sync_fallbacks"] == 0.0
            runs.append((outs, stats["megastep_launches"],
                         stats["iterations"], stats["megastep_tokens"]))
        (sync_outs, *sync_counts), (ring_outs, *ring_counts) = runs
        assert sync_counts == ring_counts and sync_counts[0] > 0
        for (prompt, horizon), a, b in zip(reqs, sync_outs, ring_outs):
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(
                a, fixed_reference(gpt2_engine, prompt, horizon))


@pytest.mark.serve_slow
class TestAsyncComposition:
    def test_chunked_prefill_composes(self, gpt2_engine):
        """Chunked prefill admits mid-flight rows whose true last token
        lives on host while a launch is in flight — the fresh-token
        device merge must keep them bit-identical."""
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab, seed=7)
        kwargs = dict(num_slots=8, max_total_len=32)
        with ContinuousScheduler(gpt2_engine, **kwargs) as sched:
            baseline = _run_all(sched, reqs)
        with ContinuousScheduler(gpt2_engine, prefill_budget=4, megastep=4,
                                 async_decode=True, async_depth=_DEPTH,
                                 **kwargs) as sched:
            stacked = _run_all(sched, reqs)
            stats = sched.stats()
            assert stats["prefill_chunks"] > len(reqs)
            # Final chunks ride the ring now: chunked prefill no longer
            # flushes the pipeline every iteration.
            assert stats["async_sync_fallbacks"] == 0.0
        for base, out in zip(baseline, stacked):
            np.testing.assert_array_equal(out, base)

    def test_prefix_cache_composes(self, gpt2_engine):
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(13)
        prefix = rng.integers(0, vocab, size=(8,), dtype=np.int32)
        reqs = [(np.concatenate([prefix, rng.integers(
                     0, vocab, size=(n,), dtype=np.int32)]), 3)
                for n in (4, 6, 9)]
        kwargs = dict(num_slots=8, max_total_len=32, cache_mode="paged",
                      block_size=4, prefix_cache=True)
        runs = []
        for async_on in (False, True):
            with ContinuousScheduler(gpt2_engine, megastep=8,
                                     async_decode=async_on,
                                     async_depth=_DEPTH,
                                     **kwargs) as sched:
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

    def test_spec_decoding_composes(self, gpt2_engine):
        """Speculative drafts build from the N-1 fetched view and verify
        against the device-resident carry, so spec_k rides the ring
        instead of flushing it: zero sync fallbacks, real verify
        launches, and greedy output bit-identical to the classic
        scheduler (the drafter is correctness-neutral — a stale draft
        only costs acceptance, never tokens).  Horizons are long and
        prompts self-repeating: the ring budgets worst-case in-flight
        tokens against the horizon, so at depth 4 a short request never
        has draft room — drafts need ``max_new_tokens`` comfortably
        past ``(depth - 1) * (spec_k + 1)``, and the doubled prompt
        guarantees the n-gram drafter a hit."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(11)
        reqs = []
        for length, horizon in ((4, 12), (6, 16), (9, 14),
                                (8, 15), (5, 13), (6, 16)):
            base = rng.integers(0, vocab, size=(length,), dtype=np.int32)
            reqs.append((np.concatenate([base, base]), horizon))
        # The classic scheduler's rows are as long as the speculating one's
        # (``max_total_len + spec_k``): one cache shape, so the second run
        # is served by the prefill programs of the first.
        with ContinuousScheduler(gpt2_engine, num_slots=8,
                                 max_total_len=64 + 2) as sched:
            baseline = _run_all(sched, reqs)
        with ContinuousScheduler(gpt2_engine, spec_k=2, async_decode=True,
                                 async_depth=_DEPTH, num_slots=8,
                                 max_total_len=64) as sched:
            specced = _run_all(sched, reqs)
            stats = sched.stats()
            assert stats["async_sync_fallbacks"] == 0.0
            assert stats["spec_launches"] > 0
        for base, out in zip(baseline, specced):
            np.testing.assert_array_equal(out, base)

    def test_reload_pins_admission_generation(self, gpt2_engine):
        """Weights staged while a launch is in flight must not touch the
        in-flight request: it decodes every remaining launch on the
        generation pinned at admission, and the reload lands for the
        NEXT admission."""
        import time

        vocab = gpt2_engine.module.cfg.vocab_size
        whale = (np.arange(64, dtype=np.int32) * 3) % vocab
        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=96,
                                 prefill_budget=2, megastep=4,
                                 async_decode=True) as sched:
            gen0 = sched.generation
            fut = sched.submit(whale, max_new_tokens=6)
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                s = sched.stats()
                if s["prefilling_slots"] >= 1.0 and s["prefill_chunks"] >= 1:
                    break
                time.sleep(0.001)
            else:
                pytest.fail("whale never observed mid-prefill")
            sched.update_params(gpt2_engine.params, generation=gen0 + 7)
            out = fut.result(timeout=300)
            assert fut.generation == gen0
            post = sched.submit(whale[:4], max_new_tokens=6)
            post.result(timeout=300)
            assert post.generation == gen0 + 7
            assert sched.generation == gen0 + 7
        np.testing.assert_array_equal(
            out, fixed_reference(gpt2_engine, whale, 6))


@pytest.mark.serve_slow
class TestLaunchRing:
    """Depth > 2: the ring holds several launches in flight and the
    dedicated fetch thread resolves them strictly in dispatch order."""

    def test_depth4_parity_and_ring_occupancy(self, gpt2_engine):
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab, seed=23)
        kwargs = dict(num_slots=8, max_total_len=32, cache_mode="paged",
                      block_size=4)
        with ContinuousScheduler(gpt2_engine, **kwargs) as sched:
            baseline = _run_all(sched, reqs)
        with ContinuousScheduler(gpt2_engine, megastep=2, async_decode=True,
                                 async_depth=4, **kwargs) as sched:
            deep = _run_all(sched, reqs)
            stats = sched.stats()
            assert stats["async_depth"] == 4.0
            assert stats["async_sync_fallbacks"] == 0.0
            # The free-running loop must actually have used the extra
            # head-room at least once, and never exceeded it.
            assert 2.0 <= stats["async_ring_depth_max"] <= 4.0
        for base, out in zip(baseline, deep):
            np.testing.assert_array_equal(out, base)

    def test_depth4_defers_resolution_in_launch_order(self, gpt2_engine):
        """Manual stepping at depth 4: the deferred prefill record
        resolves via the progress rule (nothing else is dispatchable),
        then decode dispatches D1..D3 stack up with no fetch; the 4th
        decode dispatch resolves exactly D1 (launch order), so the
        request's token count jumps by ONE megastep, not three."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(29)
        prompt = rng.integers(0, vocab, size=(4,), dtype=np.int32)
        sched = ContinuousScheduler(gpt2_engine, num_slots=8,
                                    max_total_len=32, megastep=2,
                                    async_decode=True, async_depth=4,
                                    start=False)
        try:
            fut = sched.submit(prompt, max_new_tokens=12)

            def ntok():
                with sched._lock:
                    return len(next(iter(sched._active.values())).tokens)

            sched._iteration()          # it1: prefill -> progress-resolve
            assert ntok() == 1 and len(sched._ring) == 0
            sched._iteration()          # it2: dispatch D1 — no fetch yet
            assert ntok() == 1 and len(sched._ring) == 1
            sched._iteration()          # it3: dispatch D2 — no fetch yet
            sched._iteration()          # it4: dispatch D3 — no fetch yet
            assert ntok() == 1 and len(sched._ring) == 3
            sched._iteration()          # it5: dispatch D4 -> resolve D1
            assert ntok() == 3          # prefill + D1's two tokens only
            assert len(sched._ring) == 3
            n = 0
            while not fut.done() and n < 40:
                sched._iteration()
                n += 1
            out = np.asarray(fut.result(timeout=60))
        finally:
            sched.close(timeout=5.0)
        assert not sched._ring          # close() drained the ring
        np.testing.assert_array_equal(
            out, fixed_reference(gpt2_engine, prompt, 12))

    def test_on_token_streams_post_trim_in_order(self, gpt2_engine):
        """``on_token`` fires per resolved megastep AFTER horizon trim
        with the list of newly decoded tokens: concatenated, the
        streamed sequence is exactly the final result, in order — an
        out-of-order fetch or an untrimmed ragged tail would both show
        up here."""
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab, seed=31)
        streamed = [[] for _ in reqs]
        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=32,
                                 megastep=4, async_decode=True,
                                 async_depth=4) as sched:
            futs = [sched.submit(p, max_new_tokens=m,
                                 on_token=streamed[i].extend)
                    for i, (p, m) in enumerate(reqs)]
            outs = [f.result(timeout=300) for f in futs]
        for (prompt, horizon), got, out in zip(reqs, streamed, outs):
            assert len(got) == horizon == len(out)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(out))

    def test_fetch_thread_clean_shutdown(self, gpt2_engine):
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab, seed=37)
        sched = ContinuousScheduler(gpt2_engine, num_slots=8,
                                    max_total_len=32, megastep=2,
                                    async_decode=True, async_depth=4)
        try:
            _run_all(sched, reqs)
            fetcher = sched._fetch_thread
            assert fetcher is not None and fetcher.is_alive()
        finally:
            sched.close(timeout=10.0)
        assert not fetcher.is_alive()
        assert sched._fetch_q.empty()
        assert not sched._ring
        sched.close(timeout=1.0)  # idempotent

    def test_cancel_mid_ring_frees_blocks_once(self, gpt2_engine):
        """Regression: ``cancel(rid)`` with >= 2 launches in flight must
        retire at the fetch boundary — the whole ring drains first (so
        freed blocks can't take a zombie device write), the KV blocks
        release exactly once, and the survivor's output is untouched."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(41)
        prompt_a = rng.integers(0, vocab, size=(4,), dtype=np.int32)
        prompt_b = rng.integers(0, vocab, size=(4,), dtype=np.int32)
        sched = ContinuousScheduler(gpt2_engine, num_slots=8,
                                    max_total_len=32, cache_mode="paged",
                                    block_size=4, megastep=2,
                                    async_decode=True, async_depth=4,
                                    start=False)
        try:
            fut_a = sched.submit(prompt_a, max_new_tokens=12)
            fut_b = sched.submit(prompt_b, max_new_tokens=12)
            sched._iteration()          # prefill both + dispatch D1
            sched._iteration()          # dispatch D2
            sched._iteration()          # dispatch D3
            assert len(sched._ring) >= 2
            assert sched.cancel(fut_b.rid) is True
            n = 0
            while not (fut_a.done() and fut_b.done()) and n < 40:
                sched._iteration()
                n += 1
            with pytest.raises(Exception) as ei:
                fut_b.result(timeout=60)
            assert "cancel" in type(ei.value).__name__.lower()
            out_a = np.asarray(fut_a.result(timeout=60))
            stats = sched.stats()
            # Every block back in the pool exactly once: a double free
            # would under-run blocks_in_use or poison the free list for
            # the next admission.
            assert stats["blocks_in_use"] == 0.0
            fut_c = sched.submit(prompt_b, max_new_tokens=4)
            n = 0
            while not fut_c.done() and n < 40:
                sched._iteration()
                n += 1
            fut_c.result(timeout=60)
        finally:
            sched.close(timeout=5.0)
        np.testing.assert_array_equal(
            out_a, fixed_reference(gpt2_engine, prompt_a, 12))


@pytest.mark.serve_slow
class TestAdmissionLag:
    """The one semantic async DOES change, pinned by manually stepping
    the loop: iteration order is host_sched -> dispatch D_N -> fetch
    D_{N-1}, so a request submitted while megastep N is in flight
    prefills at N+1, rides launch D_{N+1}, and sees its first decoded
    tokens only at iteration N+2's fetch."""

    def _trace(self, engine, async_on):
        vocab = engine.module.cfg.vocab_size
        rng = np.random.default_rng(21)
        prompt_a = rng.integers(0, vocab, size=(4,), dtype=np.int32)
        prompt_b = rng.integers(0, vocab, size=(4,), dtype=np.int32)
        sched = ContinuousScheduler(engine, num_slots=8, max_total_len=16,
                                    megastep=4, async_decode=async_on,
                                    start=False)
        try:
            fut_a = sched.submit(prompt_a, max_new_tokens=6)
            sched._iteration()   # it1: admit+prefill A, dispatch D1
            fut_b = sched.submit(prompt_b, max_new_tokens=6)  # during D1
            sched._iteration()   # it2: admit+prefill B, dispatch D2,
            #                      fetch D1 (sync mode fetches D2 here)
            with sched._lock:
                lens = {r.rid: len(r.tokens)
                        for r in sched._active.values()}
            b_after_it2 = lens[fut_b.rid]
            n = 0
            while not (fut_a.done() and fut_b.done()) and n < 40:
                sched._iteration()
                n += 1
            return (b_after_it2, np.asarray(fut_a.result(timeout=60)),
                    np.asarray(fut_b.result(timeout=60)))
        finally:
            sched.close(timeout=1.0)

    def test_one_iteration_admission_lag(self, gpt2_engine):
        b_async, out_a, out_b = self._trace(gpt2_engine, True)
        b_sync, ref_a, ref_b = self._trace(gpt2_engine, False)
        # Async: after it2, B holds ONLY its prefill token — D2's tokens
        # are still in flight and land at it3's fetch (N+2).  Sync: it2
        # fetched D2 before returning, so B already holds 1 + K tokens.
        assert b_async == 1
        assert b_sync == 5
        # The lag re-times delivery; it never changes the tokens.
        np.testing.assert_array_equal(out_a, ref_a)
        np.testing.assert_array_equal(out_b, ref_b)


class TestAutotune:
    """The control law, against a stubbed timing source: K is the
    smallest power of two with dispatch <= K * step / 2, clamped to
    [1, 32], frozen at the first confident pick."""

    @pytest.mark.parametrize("dispatch_ms,step_ms,expect_k", [
        (8.0, 1.0, 16),     # 2a/b = 16, exact power of two
        (3.0, 1.0, 8),      # 2a/b = 6 -> next power of two up
        (1000.0, 1.0, 32),  # absurd ratio clamps at the ceiling
        (0.01, 1.0, 1),     # dispatch already cheap: stay classic
    ])
    def test_control_law_stubbed(self, gpt2_engine, dispatch_ms, step_ms,
                                 expect_k):
        sched = ContinuousScheduler(gpt2_engine, num_slots=8,
                                    max_total_len=32, megastep="auto",
                                    start=False)
        try:
            sched._dispatch_s.extend([dispatch_ms / 1e3] * 8)
            sched._step_s.extend([step_ms / 1e3] * 8)
            sched._autotune_eval()
            assert sched.megastep == expect_k
            assert sched.stats()["megastep_autotune_frozen"] == 1.0
        finally:
            sched.close(timeout=0.1)

    def test_too_few_samples_never_freezes(self, gpt2_engine):
        sched = ContinuousScheduler(gpt2_engine, num_slots=8,
                                    max_total_len=32, megastep="auto",
                                    start=False)
        try:
            sched._dispatch_s.extend([0.008] * 7)  # one short of the bar
            sched._step_s.extend([0.001] * 8)
            sched._autotune_eval()
            assert sched.megastep == 1
            assert sched.stats()["megastep_autotune_frozen"] == 0.0
        finally:
            sched.close(timeout=0.1)

    @pytest.mark.serve_slow
    def test_auto_converges_under_traffic(self, gpt2_engine):
        """Real traffic: enough iterations to freeze, a K in range, and
        greedy parity across the mid-stream K switch."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(17)
        reqs = [(rng.integers(0, vocab, size=(6,), dtype=np.int32), 24)
                for _ in range(4)]
        kwargs = dict(num_slots=8, max_total_len=32)
        with ContinuousScheduler(gpt2_engine, **kwargs) as sched:
            baseline = _run_all(sched, reqs)
        with ContinuousScheduler(gpt2_engine, megastep="auto",
                                 async_decode=True, **kwargs) as sched:
            tuned = _run_all(sched, reqs)
            stats = sched.stats()
            assert stats["megastep_autotune_frozen"] == 1.0
            assert 1 <= stats["megastep"] <= 32
        for base, out in zip(baseline, tuned):
            np.testing.assert_array_equal(out, base)
