"""Open-loop load-harness tests: trace construction is deterministic and
scenario-shaped, ``run_trace`` stays open-loop (shed never blocks the
arrival clock, 429s count against goodput), and the real-engine smoke
drives a tiny continuous scheduler end to end with the lifecycle
recorder attached.

The pure-host cases (trace building, spec parsing, fake-backend
scoring) are tier-1 cheap; the engine smoke rides the shared module
``gpt2_engine`` the other serve suites already pay for.
"""

import time
from concurrent.futures import Future

import numpy as np
import pytest

from distributed_tensorflow_tpu.serve import ServeEngine
from distributed_tensorflow_tpu.serve.batcher import ServeOverloadedError
from distributed_tensorflow_tpu.serve.loadgen import (
    TraceRequest,
    build_trace,
    parse_trace_spec,
    run_trace,
    tier_name,
)

VOCAB = 64


@pytest.fixture(scope="module")
def gpt2_engine(request):
    mesh_dp = request.getfixturevalue("mesh_dp")
    eng = ServeEngine("gpt2", mesh=mesh_dp, preset="tiny")
    yield eng
    eng.close()


class TestBuildTrace:
    def test_same_seed_same_trace(self):
        a = build_trace(40, seed=3, vocab=VOCAB)
        b = build_trace(40, seed=3, vocab=VOCAB)
        assert len(a) == len(b) == 40
        for ra, rb in zip(a, b):
            assert ra.at == rb.at
            assert np.array_equal(ra.prompt, rb.prompt)
            assert (ra.scenario, ra.priority, ra.group, ra.turn) == \
                (rb.scenario, rb.priority, rb.group, rb.turn)

    def test_different_seed_differs(self):
        a = build_trace(40, seed=3, vocab=VOCAB)
        b = build_trace(40, seed=4, vocab=VOCAB)
        assert any(not np.array_equal(ra.prompt, rb.prompt)
                   for ra, rb in zip(a, b))

    @pytest.mark.parametrize("process", ["poisson", "diurnal", "burst"])
    def test_arrivals_sorted_and_positive(self, process):
        trace = build_trace(32, seed=1, process=process, rate=20.0,
                            vocab=VOCAB)
        ats = [r.at for r in trace]
        assert ats == sorted(ats)
        assert all(t >= 0.0 for t in ats)

    def test_chat_turns_resubmit_grown_prefix(self):
        trace = build_trace(60, seed=7, vocab=VOCAB, chat_frac=0.9,
                            whale_frac=0.0, shared_frac=0.0)
        convs = {}
        for r in trace:
            if r.scenario == "chat":
                convs.setdefault(r.group, []).append(r)
        assert convs, "no chat conversations drawn"
        grown = 0
        for turns in convs.values():
            turns.sort(key=lambda r: r.turn)
            for prev, nxt in zip(turns, turns[1:]):
                assert len(nxt.prompt) > len(prev.prompt)
                assert np.array_equal(nxt.prompt[:len(prev.prompt)],
                                      prev.prompt)
                grown += 1
        assert grown > 0

    def test_shared_groups_share_prefix(self):
        trace = build_trace(60, seed=9, vocab=VOCAB, shared_frac=0.9,
                            whale_frac=0.0, chat_frac=0.0, short_len=8)
        groups = {}
        for r in trace:
            if r.scenario == "shared":
                groups.setdefault(r.group, []).append(r)
        multi = [g for g in groups.values() if len(g) > 1]
        assert multi, "no multi-member shared groups drawn"
        for members in multi:
            head = members[0].prompt[:8]
            assert all(np.array_equal(m.prompt[:8], head)
                       for m in members)

    def test_tier_deadlines_applied(self):
        trace = build_trace(64, seed=5, vocab=VOCAB)
        for r in trace:
            tier = tier_name(r.priority)
            if tier == "batch":
                assert r.ttft_deadline_ms is None
            else:
                assert r.ttft_deadline_ms > 0
            assert r.tpot_deadline_ms > 0

    def test_max_total_len_clamps_prompts(self):
        trace = build_trace(64, seed=5, vocab=VOCAB, whale_frac=0.5,
                            whale_len=64, whale_new=16, max_total_len=32)
        assert all(len(r.prompt) + 0 <= 32 - r.max_new_tokens
                   or len(r.prompt) == 1 for r in trace)
        assert all(len(r.prompt) >= 1 for r in trace)


class TestParseTraceSpec:
    def test_defaults_and_overrides(self):
        kw = parse_trace_spec("poisson:n=24,rate=12,whale_frac=0.3",
                              rate=8.0, seed=2)
        assert kw["process"] == "poisson"
        assert kw["n"] == 24 and kw["rate"] == 12
        assert kw["whale_frac"] == pytest.approx(0.3)
        assert kw["seed"] == 2

    def test_bare_process_uses_argument_rate(self):
        kw = parse_trace_spec("burst", rate=5.0, seed=0)
        assert kw["process"] == "burst" and kw["rate"] == 5.0
        assert kw["n"] == 64

    def test_bad_pair_raises(self):
        with pytest.raises(ValueError, match="key=value"):
            parse_trace_spec("poisson:rate")

    def test_unknown_process_raises_at_build(self):
        kw = parse_trace_spec("sawtooth:n=4")
        n = kw.pop("n")
        with pytest.raises(ValueError, match="arrival process"):
            build_trace(n, **kw)


class _FakeBackend:
    """Scriptable backend: sheds every ``shed_every``-th submission and
    streams ``new`` tokens immediately for the rest."""

    def __init__(self, *, shed_every=0, new=3):
        self.shed_every = shed_every
        self.new = new
        self.submissions = 0
        self.sampling_seen = []

    def submit(self, prompt, *, max_new_tokens, sampling=None,
               on_token=None):
        self.submissions += 1
        if self.shed_every and self.submissions % self.shed_every == 0:
            raise ServeOverloadedError("queue full; back off and retry")
        self.sampling_seen.append(dict(sampling or {}))
        toks = list(range(self.new))
        if on_token is not None:
            on_token(toks)
        fut = Future()
        fut.set_result(np.asarray(toks, np.int32))
        return fut


class TestRunTraceOpenLoop:
    def _trace(self, n=12, rate=500.0):
        return build_trace(n, seed=1, rate=rate, vocab=VOCAB)

    def test_shed_counts_against_goodput_and_never_blocks(self):
        backend = _FakeBackend(shed_every=3)
        trace = self._trace(12)
        t0 = time.monotonic()
        report = run_trace(backend, trace, speed=1e4)
        assert time.monotonic() - t0 < 10.0
        assert report["requests_total"] == 12
        assert report["shed"] == 4
        assert report["shed_rate"] == pytest.approx(4 / 12)
        # Every non-shed request completed instantly -> met its SLO.
        assert report["completed"] == 8
        assert report["goodput_under_slo"] == pytest.approx(8 / 12)

    def test_priority_and_deadline_ride_sampling(self):
        backend = _FakeBackend()
        run_trace(backend, self._trace(10), speed=1e4)
        assert len(backend.sampling_seen) == 10
        assert all("priority" in s for s in backend.sampling_seen)
        assert any("deadline_ms" in s for s in backend.sampling_seen)

    def test_tokens_checksum_stable_across_replays(self):
        trace = self._trace(10)
        a = run_trace(_FakeBackend(), trace, speed=1e4)
        b = run_trace(_FakeBackend(), trace, speed=1e4)
        assert a["tokens_checksum"] == b["tokens_checksum"]
        c = run_trace(_FakeBackend(new=4), trace, speed=1e4)
        assert c["tokens_checksum"] != a["tokens_checksum"]

    def test_report_schema(self):
        report = run_trace(_FakeBackend(), self._trace(8), speed=1e4)
        for key in ("requests_total", "completed", "shed", "errors",
                    "shed_rate", "goodput_under_slo", "tokens_emitted",
                    "wall_s", "tokens_per_sec", "client_ttft_p50_ms",
                    "client_ttft_p99_ms", "tokens_checksum", "by_tier",
                    "by_scenario"):
            assert key in report, key
        assert sum(report["by_scenario"].values()) == 8

    def test_speed_must_be_positive(self):
        with pytest.raises(ValueError, match="speed"):
            run_trace(_FakeBackend(), self._trace(2), speed=0.0)


class TestEngineSmoke:
    def test_trace_drives_scheduler_with_lifecycle(self, gpt2_engine):
        from distributed_tensorflow_tpu.obs.lifecycle import (
            LifecycleRecorder,
        )
        from distributed_tensorflow_tpu.obs.metrics import Registry
        from distributed_tensorflow_tpu.serve import ContinuousScheduler

        vocab = gpt2_engine.module.cfg.vocab_size
        trace = build_trace(6, seed=13, rate=100.0, vocab=vocab,
                            short_len=4, short_new=4, whale_frac=0.0,
                            chat_frac=0.0, shared_frac=0.0,
                            max_total_len=16)
        rec = LifecycleRecorder(registry=Registry())
        sched = ContinuousScheduler(gpt2_engine, num_slots=2,
                                    max_total_len=16, lifecycle=rec)
        try:
            report = run_trace(sched, trace, speed=1e3, lifecycle=rec)
        finally:
            sched.close()
            rec.close()
        assert report["completed"] == 6 and report["shed"] == 0
        assert report["tokens_emitted"] == 6 * 4
        lc = report["lifecycle"]
        assert lc["lifecycle_requests_total"] == 6.0
        assert lc["breakdown_sum_to_wall_ratio"] == pytest.approx(
            1.0, abs=0.05)
        walls = rec.breakdowns()
        assert len(walls) == 6
        for b in walls:
            parts = sum(b[p] for p in ("queue_wait", "prefill",
                                       "decode_compute", "fetch_wait",
                                       "swap", "scheduler_stall"))
            assert parts == pytest.approx(b["wall"], abs=0.005)
