"""SLO-aware scheduling tests: priority/deadline admission ranking,
starvation aging, and host-RAM KV tiering (preempt -> swap -> resume)
under deliberate block pressure.

The preemption scenario: a low-priority
whale decodes in a pool sized so one resident whale leaves LESS than one
short request's worth of free blocks — a high-priority short can only
run by evicting the whale.  Greedy decode on CPU is deterministic, so
preempt/resume parity is exact array equality against the unpressured
fixed-batch reference (or, for int8 KV, against the identical paged run
without preemption).

Engine-heavy cases carry ``serve_slow`` (excluded from tier-1 alongside
``slow``); the tier-1 slice keeps one swap/resume parity run, the cheap
ordering probes, and the pure-host unit tests.
"""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from distributed_tensorflow_tpu.serve import ContinuousScheduler, ServeEngine
from distributed_tensorflow_tpu.serve import sampling as sampling_lib
from distributed_tensorflow_tpu.serve.continuous import _SlotRequest
from tests.helpers import fixed_reference

WHALE_LEN, WHALE_NEW = 8, 16   # a max-length request: 8 + 16 = MAX_TOTAL
SHORT_LEN, SHORT_NEW = 4, 8
BLOCK_SIZE = 4
MAX_TOTAL = 24
# The whale is a MAX-LENGTH request (the pool must hold one of those by
# construction), so the pool can be sized one short past it: a resident
# whale (6 blocks) leaves 2 free of the 8 usable — less than a short's
# 3 — so admitting a short REQUIRES preempting the whale.
BLOCKS_WHALE = -(-(WHALE_LEN + WHALE_NEW - 1) // BLOCK_SIZE)
BLOCKS_SHORT = -(-(SHORT_LEN + SHORT_NEW - 1) // BLOCK_SIZE)
POOL = BLOCKS_WHALE + BLOCKS_SHORT  # incl. trash block 0


@pytest.fixture(scope="module")
def gpt2_engine(request):
    mesh_dp = request.getfixturevalue("mesh_dp")
    eng = ServeEngine("gpt2", mesh=mesh_dp, preset="tiny")
    yield eng
    eng.close()


def _paged_slo_kwargs(**over):
    kw = dict(num_slots=4, max_total_len=MAX_TOTAL, cache_mode="paged",
              block_size=BLOCK_SIZE, num_blocks=POOL,
              slo_scheduling=True, swap_min_tokens=4)
    kw.update(over)
    return kw


def _pressure_run(sched, vocab, seed=11, deadline_ms=None):
    """Whale (priority 0) mid-decode, then high-priority shorts: returns
    ``(whale_pairs, short_pairs)`` of (prompt, output) after everything
    resolves.  The shorts can only admit by preempting the whale."""
    rng = np.random.default_rng(seed)
    whale = rng.integers(0, vocab, size=(WHALE_LEN,), dtype=np.int32)
    shorts = [rng.integers(0, vocab, size=(SHORT_LEN,), dtype=np.int32)
              for _ in range(3)]
    decoding = threading.Event()
    seen = [0]

    def on_tok(toks):
        seen[0] += len(toks)
        if seen[0] >= 4:
            decoding.set()

    wf = sched.submit(whale, max_new_tokens=WHALE_NEW,
                      sampling={"priority": 0}, on_token=on_tok)
    assert decoding.wait(timeout=300.0), "whale never started decoding"
    sampling = {"priority": 9}
    if deadline_ms is not None:
        sampling["deadline_ms"] = deadline_ms
    sf = [sched.submit(p, max_new_tokens=SHORT_NEW, sampling=sampling)
          for p in shorts]
    whale_out = wf.result(timeout=300.0)
    short_outs = [f.result(timeout=300.0) for f in sf]
    return [(whale, whale_out)], list(zip(shorts, short_outs))


# ---------------------------------------------------------------------------
# SamplingParams surface: priority/deadline are host-side request
# attributes, never program identity
# ---------------------------------------------------------------------------

class TestSamplingSLOFields:
    def test_priority_range_validates(self):
        sampling_lib.coerce({"priority": 0})
        sampling_lib.coerce({"priority": 9})
        for bad in (-1, 10, 3.5, True):
            with pytest.raises((ValueError, TypeError)):
                sampling_lib.coerce({"priority": bad})

    def test_deadline_validates(self):
        sampling_lib.coerce({"deadline_ms": 250.0})
        for bad in (0.0, -5.0, float("inf"), float("nan"), True):
            with pytest.raises((ValueError, TypeError)):
                sampling_lib.coerce({"deadline_ms": bad})

    def test_slo_fields_never_reach_packed_program_inputs(self):
        """pack() builds the runtime parameter vectors that ride into
        the compiled step — priority/deadline must not appear there (a
        priority change must never recompile or change program id)."""
        a = sampling_lib.coerce({"priority": 9, "deadline_ms": 100.0})
        b = sampling_lib.coerce(None)
        packed_a = sampling_lib.pack([a], 1)
        packed_b = sampling_lib.pack([b], 1)
        assert set(packed_a) == set(packed_b)
        for key in packed_a:
            np.testing.assert_array_equal(packed_a[key], packed_b[key])


# ---------------------------------------------------------------------------
# Constructor / flag validation
# ---------------------------------------------------------------------------

class TestCtorValidation:
    def test_negative_swap_min_tokens_rejected(self, gpt2_engine):
        with pytest.raises(ValueError, match="swap_min_tokens"):
            ContinuousScheduler(gpt2_engine,
                                **_paged_slo_kwargs(swap_min_tokens=-1))

    def test_nonpositive_starvation_age_rejected(self, gpt2_engine):
        with pytest.raises(ValueError, match="starvation_age_s"):
            ContinuousScheduler(gpt2_engine,
                                **_paged_slo_kwargs(starvation_age_s=0.0))

    @pytest.mark.serve_slow
    def test_dense_slo_ranks_without_tiering(self, gpt2_engine):
        """Dense mode: ranked admission works, but there is no block
        pool to reclaim — no tier pool, and preemption never fires."""
        with ContinuousScheduler(gpt2_engine, num_slots=4,
                                 max_total_len=MAX_TOTAL,
                                 slo_scheduling=True) as sched:
            prompt = np.arange(6, dtype=np.int32)
            out = sched.submit(prompt, max_new_tokens=5,
                               sampling={"priority": 7}).result(timeout=300)
            s = sched.stats()
        np.testing.assert_array_equal(
            out, fixed_reference(gpt2_engine, prompt, 5))
        assert s["slo_scheduling"] == 1.0
        assert s["preemptions_total"] == 0.0
        # Dense mode exports the uniform key set with the tier zeroed.
        assert s["swapped_resident"] == 0.0
        assert s["swap_bytes_total"] == 0.0


# ---------------------------------------------------------------------------
# Ranked admission: priority, deadline slack, starvation aging
# ---------------------------------------------------------------------------

class TestRankedAdmission:
    def _order_run(self, engine, first, second, *, starvation_age_s=5.0,
                   settle=0.0):
        """Block-pressure ordering probe.  Slots are plentiful (the
        engine buckets ``num_slots`` up to the mesh's row count), so the
        gate is the BLOCK pool: a priority-9 whale reserves 6 of the 8
        usable blocks (8 + 16 - 1 tokens / block_size 4), and each
        contender needs 5 (4 + 17 - 1) — more than half the pool, so
        once the whale retires the ranked winner admits ALONE and the
        loser waits a full retirement behind it.  The whale sits in the
        top tier, so nothing ever preempts it — this isolates admission
        RANKING from the preemption machinery.  Returns the order the
        contenders' first tokens arrived."""
        order = []

        def tracker(tag):
            fired = [False]

            def cb(toks):
                if not fired[0]:
                    fired[0] = True
                    order.append(tag)
            return cb

        with ContinuousScheduler(
                engine, **_paged_slo_kwargs(
                    starvation_age_s=starvation_age_s)) as sched:
            started = threading.Event()
            blocker = sched.submit(
                np.arange(WHALE_LEN, dtype=np.int32),
                max_new_tokens=WHALE_NEW, sampling={"priority": 9},
                on_token=lambda t: started.set())
            assert started.wait(timeout=300.0)
            fa = sched.submit(np.arange(SHORT_LEN, dtype=np.int32) + 1,
                              max_new_tokens=17, sampling=first,
                              on_token=tracker("first"))
            if settle:
                time.sleep(settle)
            fb = sched.submit(np.arange(SHORT_LEN, dtype=np.int32) + 2,
                              max_new_tokens=17, sampling=second,
                              on_token=tracker("second"))
            blocker.result(timeout=300.0)
            fa.result(timeout=300.0)
            fb.result(timeout=300.0)
            s = sched.stats()
        assert s["preemptions_total"] == 0.0  # top-tier whale: rank only
        return order

    @pytest.mark.serve_slow
    def test_higher_priority_admits_first(self, gpt2_engine):
        order = self._order_run(gpt2_engine, {"priority": 1},
                                {"priority": 9})
        assert order == ["second", "first"]

    def test_deadline_slack_breaks_priority_ties(self, gpt2_engine):
        order = self._order_run(gpt2_engine,
                                {"priority": 5, "deadline_ms": 60_000.0},
                                {"priority": 5, "deadline_ms": 500.0})
        assert order == ["second", "first"]

    def test_starvation_aging_lifts_waiting_request(self, gpt2_engine):
        """A priority-0 request that has waited 15 aging steps outranks
        a fresh priority-8 arrival."""
        order = self._order_run(gpt2_engine, {"priority": 0},
                                {"priority": 8},
                                starvation_age_s=0.01, settle=0.15)
        assert order == ["first", "second"]

    def test_eff_priority_and_rank_key_formula(self, gpt2_engine):
        """The deterministic half of aging/slack — no timing: effective
        priority climbs one tier per starvation_age_s and clamps at 9;
        rank orders by (priority desc, slack asc, arrival)."""
        with ContinuousScheduler(
                gpt2_engine, **_paged_slo_kwargs(
                    starvation_age_s=0.05)) as sched:
            def req(prio, deadline_ms=None, submitted=100.0):
                s = {"priority": prio}
                if deadline_ms is not None:
                    s["deadline_ms"] = deadline_ms
                return _SlotRequest(
                    prompt=np.zeros(4, np.int32), max_new_tokens=4,
                    eos_token=None, future=Future(), submitted=submitted,
                    sampling=sampling_lib.coerce(s))

            r = req(2)
            assert sched._eff_priority(r, now=100.0) == 2
            assert sched._eff_priority(r, now=100.0 + 0.12) == 4
            assert sched._eff_priority(r, now=100.0 + 60.0) == 9
            # Rank comparisons inside the first aging step (0.02s of
            # wait), so raw priorities are still the effective tiers.
            now = 100.02
            tight = req(5, deadline_ms=200.0)
            loose = req(5, deadline_ms=90_000.0)
            none_ = req(5)
            high = req(6)
            ranked = sorted([loose, none_, high, tight],
                            key=lambda q: sched._rank_key(q, now))
            # Identity comparison: dataclass == on numpy fields is
            # ambiguous (the _unpark_locked pitfall).
            expect = [high, tight, loose, none_]
            assert all(a is b for a, b in zip(ranked, expect))


# ---------------------------------------------------------------------------
# Preempt -> swap -> resume parity under block pressure
# ---------------------------------------------------------------------------

class TestPreemptSwapResume:
    def _assert_swap_cycle(self, stats):
        assert stats["preemptions_total"] >= 1.0
        assert stats["preempt_swapped_total"] >= 1.0
        assert stats["resumes_total"] >= 1.0
        assert stats["resume_swapped_total"] >= 1.0
        assert stats["swap_bytes_total"] > 0.0
        assert stats["swapped_resident"] == 0.0
        assert stats["preempted_pending"] == 0.0
        assert stats["blocks_in_use"] == 0.0

    def test_swap_resume_parity_mesh_dp(self, gpt2_engine):
        vocab = gpt2_engine.module.cfg.vocab_size
        with ContinuousScheduler(gpt2_engine,
                                 **_paged_slo_kwargs()) as sched:
            whales, shorts = _pressure_run(sched, vocab)
            s = sched.stats()
        for prompt, out in whales:
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, WHALE_NEW))
        for prompt, out in shorts:
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, SHORT_NEW))
        self._assert_swap_cycle(s)

    @pytest.mark.serve_slow
    def test_swap_resume_parity_bfloat16(self, gpt2_engine):
        vocab = gpt2_engine.module.cfg.vocab_size
        with ContinuousScheduler(gpt2_engine, **_paged_slo_kwargs(
                kv_dtype="bfloat16")) as sched:
            whales, shorts = _pressure_run(sched, vocab, seed=5)
            s = sched.stats()
        for prompt, out in whales:
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, WHALE_NEW))
        for prompt, out in shorts:
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, SHORT_NEW))
        self._assert_swap_cycle(s)

    @pytest.mark.serve_slow
    def test_swap_resume_parity_int8_scales_travel(self, gpt2_engine):
        """int8 KV quantizes, so the reference is the SAME paged int8
        pool without SLO pressure (one request at a time): the swap
        round-trip must reproduce those tokens bit-for-bit — including
        the f32 scale tables that ride with each block."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(23)
        whale = rng.integers(0, vocab, size=(WHALE_LEN,), dtype=np.int32)
        shorts = [rng.integers(0, vocab, size=(SHORT_LEN,), dtype=np.int32)
                  for _ in range(3)]
        refs = {}
        with ContinuousScheduler(gpt2_engine, num_slots=4,
                                 max_total_len=MAX_TOTAL,
                                 cache_mode="paged", block_size=BLOCK_SIZE,
                                 num_blocks=POOL,
                                 kv_dtype="int8") as plain:
            refs["whale"] = plain.submit(
                whale, max_new_tokens=WHALE_NEW).result(timeout=300)
            refs["shorts"] = [plain.submit(
                p, max_new_tokens=SHORT_NEW).result(timeout=300)
                for p in shorts]
        with ContinuousScheduler(gpt2_engine, **_paged_slo_kwargs(
                kv_dtype="int8")) as sched:
            whales, short_pairs = _pressure_run(sched, vocab, seed=23)
            s = sched.stats()
        np.testing.assert_array_equal(whales[0][1], refs["whale"])
        for (_, out), ref in zip(short_pairs, refs["shorts"]):
            np.testing.assert_array_equal(out, ref)
        self._assert_swap_cycle(s)

    @pytest.mark.serve_slow
    def test_recompute_path_parity(self, gpt2_engine):
        """swap_min_tokens above any context length forces the
        recompute path: nothing moves through the host tier, the
        whale's history folds into its prompt, parity still holds."""
        vocab = gpt2_engine.module.cfg.vocab_size
        with ContinuousScheduler(gpt2_engine, **_paged_slo_kwargs(
                swap_min_tokens=10_000)) as sched:
            whales, shorts = _pressure_run(sched, vocab, seed=7)
            s = sched.stats()
        for prompt, out in whales:
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, WHALE_NEW))
        for prompt, out in shorts:
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, SHORT_NEW))
        assert s["preemptions_total"] >= 1.0
        assert s["preempt_recompute_total"] >= 1.0
        assert s["preempt_swapped_total"] == 0.0
        assert s["swap_bytes_total"] == 0.0
        assert s["blocks_in_use"] == 0.0

    @pytest.mark.serve_slow
    def test_parity_under_tensor_parallel_mesh(self, mesh_2d):
        """Swap/resume on data=4 x tensor=2: block gathers cross the
        tensor-sharded pool heads; parity must survive the host
        round-trip of sharded leaves."""
        with ServeEngine("gpt2", mesh=mesh_2d, preset="tiny") as eng:
            vocab = eng.module.cfg.vocab_size
            with ContinuousScheduler(eng, **_paged_slo_kwargs()) as sched:
                whales, shorts = _pressure_run(sched, vocab, seed=13)
                s = sched.stats()
            for prompt, out in whales:
                np.testing.assert_array_equal(
                    out, fixed_reference(eng, prompt, WHALE_NEW))
            for prompt, out in shorts:
                np.testing.assert_array_equal(
                    out, fixed_reference(eng, prompt, SHORT_NEW))
            self._assert_swap_cycle(s)

    @pytest.mark.serve_slow
    def test_preempt_composes_with_megastep_async(self, gpt2_engine):
        """Preemption lands at an iteration boundary even when decode
        runs K fused steps per launch with async double-buffering —
        the whale's written-positions anchor survives both."""
        vocab = gpt2_engine.module.cfg.vocab_size
        with ContinuousScheduler(gpt2_engine, **_paged_slo_kwargs(
                megastep=4, async_decode=True)) as sched:
            whales, shorts = _pressure_run(sched, vocab, seed=19)
            s = sched.stats()
        for prompt, out in whales:
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, WHALE_NEW))
        for prompt, out in shorts:
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, SHORT_NEW))
        assert s["preemptions_total"] >= 1.0
        assert s["blocks_in_use"] == 0.0
        assert s["swapped_resident"] == 0.0


# ---------------------------------------------------------------------------
# Hot reload invalidates parked payloads
# ---------------------------------------------------------------------------

class TestHotReloadInvalidation:
    @pytest.mark.serve_slow
    def test_generation_swap_drops_parked_kv(self, gpt2_engine):
        """A weight reload while the whale is parked drops its swapped
        payload (cached K/V is a function of the weights that wrote it)
        and the whale resumes via recompute on the new generation.  The
        new generation carries the SAME values, so parity still holds —
        only the resume PATH changes."""
        vocab = gpt2_engine.module.cfg.vocab_size
        with ContinuousScheduler(gpt2_engine,
                                 **_paged_slo_kwargs()) as sched:
            gen0 = sched.generation
            rng = np.random.default_rng(31)
            whale = rng.integers(0, vocab, size=(WHALE_LEN,),
                                 dtype=np.int32)
            shorts = [rng.integers(0, vocab, size=(SHORT_LEN,),
                                   dtype=np.int32) for _ in range(3)]
            decoding = threading.Event()
            seen = [0]

            def on_tok(toks):
                seen[0] += len(toks)
                if seen[0] >= 4:
                    decoding.set()

            wf = sched.submit(whale, max_new_tokens=WHALE_NEW,
                              sampling={"priority": 0}, on_token=on_tok)
            assert decoding.wait(timeout=300.0)
            sf = [sched.submit(p, max_new_tokens=16,
                               sampling={"priority": 9}) for p in shorts]
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                s = sched.stats()
                if (s["preempt_swapped_total"] >= 1.0
                        and s["preempted_pending"] >= 1.0):
                    break
                time.sleep(0.0005)
            else:
                pytest.fail("whale never observed parked in the host tier")
            sched.update_params(gpt2_engine.params, generation=gen0 + 1)
            whale_out = wf.result(timeout=300.0)
            for f in sf:
                f.result(timeout=300.0)
            s = sched.stats()
        np.testing.assert_array_equal(
            whale_out, fixed_reference(gpt2_engine, whale, WHALE_NEW))
        assert s["preempt_swapped_total"] >= 1.0
        assert s["swap_dropped_total"] >= 1.0
        assert s["resume_swapped_total"] == 0.0
        assert s["swapped_resident"] == 0.0


# ---------------------------------------------------------------------------
# Stats surface
# ---------------------------------------------------------------------------

class TestStatsSurface:
    SLO_KEYS = ("slo_scheduling", "preemptions_total",
                "preempt_swapped_total", "preempt_recompute_total",
                "resumes_total", "resume_swapped_total",
                "preempted_pending", "deadline_met_total",
                "deadline_missed_total", "deadline_goodput")

    def test_slo_counters_present_and_zero_when_idle(self, gpt2_engine):
        with ContinuousScheduler(gpt2_engine,
                                 **_paged_slo_kwargs()) as sched:
            s = sched.stats()
        assert s["slo_scheduling"] == 1.0
        for key in self.SLO_KEYS[1:]:
            assert s[key] == 0.0, key
        assert s["swapped_resident"] == 0.0

    def test_deadline_scoring_works_without_slo_scheduling(
            self, gpt2_engine):
        """Deadline accounting keys off deadline_ms alone, so a FIFO
        scheduler scores goodput too — the off arm of any SLO A/B."""
        with ContinuousScheduler(gpt2_engine, num_slots=4,
                                 max_total_len=MAX_TOTAL) as sched:
            prompt = np.arange(5, dtype=np.int32)
            sched.submit(prompt, max_new_tokens=4,
                         sampling={"deadline_ms": 60_000.0}
                         ).result(timeout=300)
            s = sched.stats()
        assert s["slo_scheduling"] == 0.0
        assert s["deadline_met_total"] == 1.0
        assert s["deadline_missed_total"] == 0.0
        assert s["deadline_goodput"] == 1.0
