"""Megastep-decode tests: fusing K decode iterations into one compiled
``lax.scan`` program must be a pure DISPATCH change — on-device sampling,
EOS masking and horizon countdown reproduce the host loop step for step,
so greedy output is bit-identical K on vs off — while the amortization it
buys is real: one launch and one fetch cover up to K tokens per slot.

Parity runs on BOTH acceptance meshes (pure data-parallel and
data=4 x tensor=2) and in dense AND paged cache modes, including a K
that does not divide the decode horizons (megastep carries chained
across program boundaries); composition tests pin the invariants
against chunked prefill, the prefix cache, and hot weight reload at a
megastep boundary.  EOS fired at an inner scan step j < K must trim on
host to the exact K=1 output — no post-EOS token leaks."""

import contextlib
import time

import numpy as np
import pytest

from distributed_tensorflow_tpu.models.gpt2 import GPT2Config
from distributed_tensorflow_tpu.serve import ContinuousScheduler, ServeEngine


def _mixed_requests(vocab, seed=3):
    """Mixed traffic: horizons (2, 5, 3, 4) are all < 8 (whole requests
    finish inside one K=8 megastep) and straddle K=3 (5 = 3 + 2, the
    carry chains across two scans)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i, length in enumerate((4, 6, 9, 8, 17, 5)):
        horizon = (2, 5, 3, 4)[i % 4]
        reqs.append((rng.integers(0, vocab, size=(length,), dtype=np.int32),
                     horizon))
    return reqs


def _fixed_reference(engine, prompt, max_new_tokens):
    rows = engine.bucket_rows(1)
    out = engine.generate(np.repeat(prompt[None, :], rows, axis=0),
                          max_new_tokens)
    return out[0]


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
    def test_zero_megastep_rejected(self, gpt2_engine):
        with pytest.raises(ValueError, match="megastep"):
            ContinuousScheduler(gpt2_engine, megastep=0, start=False)

    def test_stats_export_megastep(self, gpt2_engine):
        sched = ContinuousScheduler(gpt2_engine, num_slots=8,
                                    max_total_len=32, megastep=8,
                                    start=False)
        stats = sched.stats()
        assert stats["megastep"] == 8.0
        assert stats["megastep_launches"] == 0.0
        assert stats["megastep_tokens"] == 0.0
        assert stats["megastep_effective_steps"] == 0.0
        sched.close(timeout=0.1)


class TestMegastepParity:
    """Greedy output must be bit-identical K on vs off: the scan changes
    HOW MANY iterations one dispatch covers, never what any row decodes."""

    @pytest.mark.parametrize(
        "cache_mode", ["dense", "paged", "paged-unrolled", "paged-int8"])
    def test_megastep_on_off_token_identical(self, gpt2_engine, mesh_dp,
                                             cache_mode):
        """``paged-unrolled`` holds a pool a layer (``scan_layers=False``)
        where ``paged`` carries the stacked pools through the layer loop
        inside the fused steps' loop; ``paged-int8`` stores quantised K/V,
        so it is held to its own K=1 stream and not to the reference."""
        kwargs = dict(num_slots=8, max_total_len=32)
        if cache_mode != "dense":
            kwargs.update(cache_mode="paged", block_size=4)
        if cache_mode == "paged-int8":
            kwargs.update(kv_dtype="int8")
        with (ServeEngine("gpt2", mesh=mesh_dp,
                          config=GPT2Config.tiny(scan_layers=False))
              if cache_mode == "paged-unrolled"
              else contextlib.nullcontext(gpt2_engine)) as engine:
            vocab = engine.module.cfg.vocab_size
            reqs = _mixed_requests(vocab)
            with ContinuousScheduler(engine, **kwargs) as sched:
                baseline = _run_all(sched, reqs)
            # K=8 swallows every horizon whole; K=3 forces ragged chains
            # (horizon 5 = one full scan + a 2-live-step tail).
            for steps in (8, 3):
                with ContinuousScheduler(engine, megastep=steps,
                                         **kwargs) as sched:
                    fused = _run_all(sched, reqs)
                    stats = sched.stats()
                    assert stats["megastep"] == float(steps)
                    # The amortization claim: strictly fewer launches than
                    # decoded tokens (K=1 pays one launch per token).
                    assert 0 < stats["megastep_launches"] \
                        < stats["megastep_tokens"]
                for (prompt, horizon), base, out in zip(
                        reqs, baseline, fused):
                    np.testing.assert_array_equal(out, base)
                    if cache_mode != "paged-int8":
                        np.testing.assert_array_equal(
                            out, _fixed_reference(engine, prompt, horizon))

    @pytest.mark.parametrize("cache_mode", ["dense", "paged"])
    def test_parity_on_2d_mesh(self, mesh_2d, cache_mode):
        """data=4 x tensor=2: the scan body's collectives and the paged
        scatter must compose with sharded params and the tensor-sharded
        resident cache."""
        with ServeEngine("gpt2", mesh=mesh_2d, preset="tiny") as eng:
            vocab = eng.module.cfg.vocab_size
            reqs = _mixed_requests(vocab, seed=5)
            kwargs = dict(num_slots=8, max_total_len=32)
            if cache_mode == "paged":
                kwargs.update(cache_mode="paged", block_size=4)
            with ContinuousScheduler(eng, **kwargs) as sched:
                baseline = _run_all(sched, reqs)
            with ContinuousScheduler(eng, megastep=8, **kwargs) as sched:
                fused = _run_all(sched, reqs)
            for base, out in zip(baseline, fused):
                np.testing.assert_array_equal(out, base)


class TestMegastepEos:
    def test_eos_mid_megastep_trims_to_k1_output(self, gpt2_engine):
        """A row whose EOS fires at inner scan step j < K stops advancing
        ON DEVICE (the alive mask freezes its token and cache index); the
        host trim walks ``done()`` exactly like the K=1 loop, so the
        result is token-identical and nothing past EOS leaks out."""
        vocab = gpt2_engine.module.cfg.vocab_size
        prompt = (np.arange(6, dtype=np.int32) * 5) % vocab
        horizon = 6
        ref = _fixed_reference(gpt2_engine, prompt, horizon)
        # Pick the first token whose value has not appeared before it:
        # greedy decode then stops exactly there, at an inner step < K.
        eos_idx = next(i for i in range(1, len(ref))
                       if ref[i] not in ref[:i])
        eos = int(ref[eos_idx])
        outs = {}
        for steps in (1, 8):
            with ContinuousScheduler(gpt2_engine, num_slots=8,
                                     max_total_len=32,
                                     megastep=steps) as sched:
                fut = sched.submit(prompt, max_new_tokens=horizon,
                                   eos_token=eos)
                outs[steps] = np.asarray(fut.result(timeout=300))
                if steps > 1:
                    # Every decode-appended token was counted (the first
                    # generated token comes from prefill); a post-EOS
                    # leak would show up as extra megastep_tokens.
                    stats = sched.stats()
                    assert stats["megastep_tokens"] == len(
                        outs[steps]) - 1
                    # Early exit: EOS at inner step j < K stops the
                    # while_loop once every row is dead — strictly fewer
                    # effective inner steps than launches * K, instead
                    # of riding out the masked no-op tail.
                    assert 0 < stats["megastep_effective_steps"] \
                        < stats["megastep_launches"] * steps
        np.testing.assert_array_equal(outs[8], outs[1])
        assert len(outs[8]) == eos_idx + 1 < horizon  # stopped mid-scan
        assert outs[8][-1] == eos
        assert eos not in outs[8][:-1]
        np.testing.assert_array_equal(outs[8], ref[:eos_idx + 1])


class TestMegastepReload:
    def test_reload_lands_at_megastep_boundary(self, gpt2_engine):
        """Weights staged mid-request swap in only at a megastep boundary:
        the in-flight request keeps its admission generation for every
        remaining scan (params ride the per-generation launch grouping),
        while the next admission picks up the new tag."""
        vocab = gpt2_engine.module.cfg.vocab_size
        whale = (np.arange(64, dtype=np.int32) * 3) % vocab
        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=96,
                                 prefill_budget=2, megastep=4) as sched:
            gen0 = sched.generation
            fut = sched.submit(whale, max_new_tokens=6)  # 6 = 4 + 2 scans
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
            out, _fixed_reference(gpt2_engine, whale, 6))


class TestMegastepComposition:
    def test_chunked_prefill_composes(self, gpt2_engine):
        """Chunked prefill feeds admissions between megasteps; both are
        pure scheduling/dispatch changes, so stacking them stays
        bit-identical to the plain loop."""
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab, seed=7)
        kwargs = dict(num_slots=8, max_total_len=32)
        with ContinuousScheduler(gpt2_engine, **kwargs) as sched:
            baseline = _run_all(sched, reqs)
        with ContinuousScheduler(gpt2_engine, prefill_budget=4, megastep=8,
                                 **kwargs) as sched:
            stacked = _run_all(sched, reqs)
            assert sched.stats()["prefill_chunks"] > len(reqs)
        for base, out in zip(baseline, stacked):
            np.testing.assert_array_equal(out, base)

    def test_prefix_cache_composes(self, gpt2_engine):
        """Prefix-mapped blocks skip prefill, then the megastep scatter
        appends behind them through the same block tables — hits and
        output must match the K=1 paged run."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(13)
        prefix = rng.integers(0, vocab, size=(8,), dtype=np.int32)
        reqs = [(np.concatenate([prefix, rng.integers(
                     0, vocab, size=(n,), dtype=np.int32)]), 3)
                for n in (4, 6, 9)]
        kwargs = dict(num_slots=8, max_total_len=32, cache_mode="paged",
                      block_size=4, prefix_cache=True)
        runs = []
        for steps in (1, 8):
            with ContinuousScheduler(gpt2_engine, megastep=steps,
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
