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

import collections
import contextlib
import time

import jax
import numpy as np
import pytest

from distributed_tensorflow_tpu.models.gpt2 import GPT2Config, PagedKVConfig
from distributed_tensorflow_tpu.serve import ContinuousScheduler, ServeEngine
from distributed_tensorflow_tpu.serve import sampling as sampling_lib
from distributed_tensorflow_tpu.serve.sampling import SamplingParams
from tests.helpers import fixed_reference


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


def _index_leaves(cache):
    """The per-slot ``cache_index``/``position`` vectors of a cache tree."""
    out = {}

    def _grab(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("cache_index", "position"):
            out[jax.tree_util.keystr(path)] = np.asarray(jax.device_get(leaf))
        return leaf

    jax.tree_util.tree_map_with_path(_grab, cache)
    assert out
    return out


class TestSingleStepReference:
    """``engine.decode_slots`` is the single-step REFERENCE: the scheduler
    launches ``decode_megastep`` for every K, so the K=1 program it runs
    must return what the plain step returns on the same inputs — tokens,
    per-slot cache index and penalty counts, step after step."""

    SLOTS = (0, 2, 5)
    PER_REQUEST = (
        None,                                            # greedy neighbour
        SamplingParams(temperature=0.9, top_k=8),
        SamplingParams(temperature=1.1, top_k=5, seed=7,
                       presence_penalty=0.5),
    )

    @pytest.mark.parametrize("sampling", ["greedy", "per-request"])
    @pytest.mark.parametrize("cache_mode", ["dense", "paged"])
    def test_megastep_of_one_equals_decode_slots(self, gpt2_engine,
                                                 cache_mode, sampling):
        eng = gpt2_engine
        vocab = eng.module.cfg.vocab_size
        rng = np.random.default_rng(21)
        prompts = [rng.integers(0, vocab, size=(n,), dtype=np.int32)
                   for n in (4, 7, 5)]
        params = (self.PER_REQUEST if sampling == "per-request"
                  else (None,) * 3)
        key = jax.random.key(9)
        paged_kw = {}
        if cache_mode == "paged":
            pcfg = PagedKVConfig(block_size=4, num_blocks=33)
            tables = np.zeros((8, 4), np.int32)
            for slot in self.SLOTS:  # block 0 is the trash block
                tables[slot] = 1 + 4 * slot + np.arange(4)
            paged_kw = dict(paged=pcfg, block_tables=tables)

        def admitted():
            cache = (eng.init_paged_cache(8, 16, paged=paged_kw["paged"])
                     if paged_kw else eng.init_slot_cache(8, 16))
            counts = eng.init_slot_counts(8)
            last = np.zeros((8,), np.int32)
            for i, (slot, prompt) in enumerate(zip(self.SLOTS, prompts)):
                tok, cache, counts = eng.prefill_into_slots(
                    cache, prompt[None, :], [slot],
                    sampling=sampling_lib.pack([params[i]], [0]),
                    counts=counts, rng=key, counter=i, **paged_kw)
                last[slot] = int(np.asarray(jax.device_get(tok))[0])
            return cache, counts, last

        active = np.zeros((8,), bool)
        active[list(self.SLOTS)] = True
        horizon = np.where(active, 8, 0).astype(np.int32)
        row_params = [None] * 8
        for slot, sp in zip(self.SLOTS, params):
            row_params[slot] = sp
        ref_cache, ref_counts, ref_last = admitted()
        cache, counts, last = admitted()
        np.testing.assert_array_equal(last, ref_last)
        for step in range(1, 4):
            steps = [step if a else 0 for a in active]  # emitted so far
            samp = sampling_lib.pack(row_params, steps)
            ref_tok, ref_cache, ref_counts = eng.decode_slots(
                ref_cache, ref_last[:, None], active, sampling=samp,
                counts=ref_counts, rng=key, counter=10 + step, **paged_kw)
            toks, final, steps_run, _clock, cache, counts = (
                eng.decode_megastep(
                    cache, last, active, horizon, steps=1, sampling=samp,
                    counts=counts, rng=key, counter=10 + step, **paged_kw))
            ref_tok = np.asarray(jax.device_get(ref_tok))
            toks = np.asarray(jax.device_get(toks))
            assert toks.shape == (8, 1) and int(steps_run) == 1
            np.testing.assert_array_equal(toks[active, 0], ref_tok[active])
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(final))[active], ref_tok[active])
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(counts)),
                np.asarray(jax.device_get(ref_counts)))
            got, want = _index_leaves(cache), _index_leaves(ref_cache)
            assert got.keys() == want.keys()
            for name in want:
                np.testing.assert_array_equal(got[name], want[name])
            ref_last = np.where(active, ref_tok, ref_last).astype(np.int32)
            last = ref_last.copy()
            horizon = horizon - active


def test_default_scheduler_compiles_only_the_megastep_program(mesh_dp):
    """One plain-decode path: with default options (``megastep=1``,
    ``async_decode=False``) every decode program the scheduler compiled
    is a ``slot_megastep`` — the K=1 one — and the single-step reference
    ``decode_slots`` is never launched by it."""
    with ServeEngine("gpt2", mesh=mesh_dp, preset="tiny") as eng:
        reqs = _mixed_requests(eng.module.cfg.vocab_size, seed=23)
        with ContinuousScheduler(eng, num_slots=8,
                                 max_total_len=32) as sched:
            outs = _run_all(sched, reqs)
            stats = sched.stats()
        kinds = collections.Counter(
            k[0] for k in eng._generate_fns
            if isinstance(k, tuple) and str(k[0]).startswith("slot_"))
        assert set(kinds) == {"slot_prefill", "slot_megastep"}
        assert ("slot_megastep", 1, None) in eng._generate_fns
        assert kinds["slot_megastep"] == 1
        names = {fn.__name__ for fn in eng._generate_fns.values()}
        assert "decode_megastep" in names and "decode_slots" not in names
        assert stats["megastep_launches"] == stats["iterations"] > 0
        assert stats["megastep_effective_steps"] == stats["iterations"]
        for (prompt, horizon), out in zip(reqs, outs):
            np.testing.assert_array_equal(
                out, fixed_reference(eng, prompt, horizon))


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
                            out, fixed_reference(engine, prompt, horizon))

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
        ref = fixed_reference(gpt2_engine, prompt, horizon)
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
            out, fixed_reference(gpt2_engine, whale, 6))


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
