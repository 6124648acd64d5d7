"""Streaming gateway tests: the TokenStream handoff, the CancelRegistry,
the stdlib HTTP/SSE front door over a scheduler-shaped dummy backend, and
the real end-to-end contract against a tiny gpt2 ContinuousScheduler —
streamed greedy output bit-identical to the whole-response path, client
cancellation that retires the slot and frees its KV blocks (and streams
ZERO further tokens), and 429 + Retry-After admission control.

Compile-heavy parity matrices and the chunked-prefill / megastep cancel
cases carry ``serve_slow``; the tier-1 slice keeps one dense K=1 parity
run, the queued-cancel and paged KV-free regressions, and every HTTP
test (the dummy backend never touches jax).
"""

import http.client
import json
import threading
import time
from concurrent.futures import CancelledError, Future

import numpy as np
import pytest

from distributed_tensorflow_tpu.serve import (
    ContinuousScheduler,
    DynamicBatcher,
    GatewayServer,
    ServeEngine,
)
from distributed_tensorflow_tpu.serve.gateway import (
    CancelRegistry,
    DepthMeter,
    TokenStream,
)
from tests.helpers import fixed_reference


def _wait_until(pred, timeout=10.0, interval=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return bool(pred())


# ---------------------------------------------------------------------------
# TokenStream: the loop-thread -> HTTP-thread handoff
# ---------------------------------------------------------------------------

class TestTokenStream:
    def test_delivers_batches_in_order_then_final(self):
        ts = TokenStream(max_events=8)
        ts.put_tokens([1, 2])
        ts.put_tokens([3])
        ts.finish({"finish_reason": "stop"})
        assert ts.get(timeout=1) == ("token", [1, 2])
        assert ts.get(timeout=1) == ("token", [3])
        kind, data = ts.get(timeout=1)
        assert kind == "final" and data["finish_reason"] == "stop"
        assert ts.get(timeout=0.01) is None  # final taken: closed forever
        assert ts.tokens_delivered == 3

    def test_get_times_out_to_none(self):
        ts = TokenStream()
        t0 = time.monotonic()
        assert ts.get(timeout=0.05) is None
        assert time.monotonic() - t0 < 5.0

    def test_at_capacity_coalesces_lossless(self):
        """A stalled client costs queue ENTRIES, not tokens: past
        max_events new batches merge into the newest pending event."""
        ts = TokenStream(max_events=2)
        for batch in ([1], [2], [3], [4]):
            ts.put_tokens(batch)
        assert ts.pending_events() == 2
        ts.finish({"finish_reason": "stop"})
        got = []
        while True:
            kind, data = ts.get(timeout=1)
            if kind == "final":
                break
            got.extend(data)
        assert got == [1, 2, 3, 4]

    def test_first_finish_wins(self):
        ts = TokenStream()
        ts.finish({"finish_reason": "stop"})
        ts.finish({"finish_reason": "shutdown"})
        assert ts.get(timeout=1)[1]["finish_reason"] == "stop"

    def test_cancelled_finish_drops_pending_tokens(self):
        """The cancel contract: after resolution the client sees the
        final event NEXT — never more tokens."""
        meter = DepthMeter()
        ts = TokenStream(depth=meter)
        ts.put_tokens([1, 2])
        ts.put_tokens([3])
        assert meter.value() == 2
        ts.finish({"finish_reason": "cancelled"})
        kind, data = ts.get(timeout=1)
        assert kind == "final" and data["finish_reason"] == "cancelled"
        assert meter.value() == 0
        ts.put_tokens([9])  # late zombie delivery: dropped
        assert ts.get(timeout=0.01) is None

    def test_depth_meter_folds_streams(self):
        meter = DepthMeter()
        a = TokenStream(depth=meter)
        b = TokenStream(depth=meter)
        a.put_tokens([1])
        b.put_tokens([2])
        b.put_tokens([3])
        assert meter.value() == 3
        a.get(timeout=1)
        assert meter.value() == 2


class TestCancelRegistry:
    def test_register_lookup_release(self):
        reg = CancelRegistry()
        fut = Future()
        gid = reg.register(fut)
        assert gid.startswith("g-")
        assert reg.get(gid).future is fut
        assert reg.active() == 1
        reg.release(gid)
        assert reg.get(gid) is None and reg.active() == 0

    def test_cancel_runs_backend_thunk(self):
        reg = CancelRegistry()
        calls = []
        gid = reg.register(Future(), canceller=lambda: calls.append(1) or True)
        assert reg.cancel(gid) is True
        assert calls == [1]

    def test_cancel_falls_back_to_future(self):
        """A request the backend no longer knows (already shed) still
        cancels through the Future itself."""
        reg = CancelRegistry()
        fut = Future()
        gid = reg.register(fut, canceller=lambda: False)
        assert reg.cancel(gid) is True
        assert fut.cancelled()

    def test_cancel_unknown_gid(self):
        assert CancelRegistry().cancel("g-404") is False


# ---------------------------------------------------------------------------
# HTTP layer over a scheduler-shaped dummy (no jax anywhere)
# ---------------------------------------------------------------------------

class DummyBackend:
    """The iteration-level submit/cancel surface with hand-driven token
    delivery: the test IS the decode loop."""

    def __init__(self):
        self._lock = threading.Lock()
        self._next = 0
        self._reqs = {}
        self.cancel_calls = []

    def submit_payload(self, payload):
        fut = Future()
        with self._lock:
            self._next += 1
            rid = self._next
            self._reqs[rid] = {"payload": dict(payload), "future": fut,
                               "tokens": []}
        fut.rid = rid
        return fut

    def has(self, rid):
        with self._lock:
            return rid in self._reqs

    def feed(self, rid, toks):
        with self._lock:
            req = self._reqs[rid]
        cb = req["payload"].get("on_token")
        if cb is not None:
            cb(list(toks))
        req["tokens"].extend(int(t) for t in toks)

    def finish(self, rid):
        with self._lock:
            req = self._reqs[rid]
        if req["future"].set_running_or_notify_cancel():
            req["future"].set_result(
                np.asarray(req["tokens"], np.int32))

    def cancel(self, rid):
        with self._lock:
            req = self._reqs.get(rid)
        self.cancel_calls.append(rid)
        if req is None or req["future"].done():
            return False
        return req["future"].cancel()


def _connect(port, timeout=30):
    return http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)


def _post(port, path, body=None, timeout=30):
    conn = _connect(port, timeout)
    conn.request("POST", path, json.dumps(body if body is not None else {}),
                 {"Content-Type": "application/json"})
    return conn, conn.getresponse()


def _read_events(resp, stop_on_final=True, limit=2000, max_events=None):
    """Parse SSE off a close-delimited response; keepalive comments are
    skipped (they cost lines, not events).  Stops after the first
    non-``token``/non-``start`` event, or after ``max_events``."""
    events = []
    event = data = None
    while limit:
        limit -= 1
        line = resp.readline()
        if not line:
            break
        line = line.decode("utf-8").rstrip("\n")
        if line.startswith("event: "):
            event = line[len("event: "):]
        elif line.startswith("data: "):
            data = json.loads(line[len("data: "):])
        elif line == "" and event is not None:
            events.append((event, data))
            if stop_on_final and event not in ("start", "token"):
                break
            if max_events is not None and len(events) >= max_events:
                break
            event = data = None
    return events


@pytest.fixture()
def dummy_gateway():
    backend = DummyBackend()
    gw = GatewayServer(backend, port=0, max_inflight=2, keepalive_s=0.05,
                      retry_after_s=7)
    yield gw, backend
    gw.close()


class TestGatewayHTTP:
    def test_health_and_stats(self, dummy_gateway):
        gw, _ = dummy_gateway
        conn = _connect(gw.port)
        conn.request("GET", "/v1/health")
        body = json.loads(conn.getresponse().read())
        assert body["ok"] is True
        conn.close()
        conn = _connect(gw.port)
        conn.request("GET", "/v1/stats")
        stats = json.loads(conn.getresponse().read())
        conn.close()
        for key in ("gateway_inflight", "gateway_max_inflight",
                    "gateway_accepted", "gateway_throttled",
                    "gateway_disconnects", "gateway_cancel_requests",
                    "stream_queue_depth"):
            assert key in stats, stats
        assert stats["gateway_max_inflight"] == 2.0

    def test_unknown_route_404(self, dummy_gateway):
        gw, _ = dummy_gateway
        conn, resp = _post(gw.port, "/v1/nope")
        assert resp.status == 404
        conn.close()

    def test_bad_payload_400(self, dummy_gateway):
        gw, _ = dummy_gateway
        conn, resp = _post(gw.port, "/v1/generate", {"prompt": []})
        assert resp.status == 400
        assert "prompt" in json.loads(resp.read())["error"]
        conn.close()

    def test_whole_response_aggregates(self, dummy_gateway):
        gw, backend = dummy_gateway
        done = {}

        def drive():
            _wait_until(lambda: backend.has(1))
            backend.feed(1, [5, 6, 7])
            backend.finish(1)
            done["ok"] = True

        t = threading.Thread(target=drive)
        t.start()
        conn, resp = _post(gw.port, "/v1/generate",
                           {"prompt": [1, 2], "max_new_tokens": 3})
        body = json.loads(resp.read())
        conn.close()
        t.join()
        assert done.get("ok")
        assert resp.status == 200
        assert body["tokens"] == [5, 6, 7]
        assert body["finish_reason"] == "length"
        assert body["num_tokens"] == 3

    def test_streaming_sse_token_events_and_usage(self, dummy_gateway):
        gw, backend = dummy_gateway

        def drive():
            _wait_until(lambda: backend.has(1))
            backend.feed(1, [11])
            backend.feed(1, [12, 13])
            backend.finish(1)

        t = threading.Thread(target=drive)
        t.start()
        conn, resp = _post(gw.port, "/v1/generate",
                           {"prompt": [1], "max_new_tokens": 3,
                            "stream": True})
        assert resp.status == 200
        assert resp.getheader("Content-Type") == "text/event-stream"
        events = _read_events(resp)
        conn.close()
        t.join()
        assert events[0][0] == "start"
        assert events[0][1]["gid"].startswith("g-")
        assert events[0][1]["rid"] == 1
        toks = [t for kind, d in events if kind == "token"
                for t in d["tokens"]]
        assert toks == [11, 12, 13]
        kind, final = events[-1]
        assert kind == "done"
        assert final["finish_reason"] == "length"
        assert final["num_tokens"] == 3
        assert final["tokens_streamed"] == 3

    def test_saturation_429_with_retry_after(self, dummy_gateway):
        """Past max_inflight open requests the gateway answers 429 and
        names the backoff — it never queues a third time."""
        gw, backend = dummy_gateway
        open_conns = []
        for i in (1, 2):
            conn, resp = _post(gw.port, "/v1/generate",
                               {"prompt": [i], "stream": True})
            assert resp.status == 200
            open_conns.append((conn, resp))
        assert _wait_until(lambda: gw.stats()["gateway_inflight"] == 2.0)
        conn, resp = _post(gw.port, "/v1/generate", {"prompt": [9]})
        assert resp.status == 429
        assert resp.getheader("Retry-After") == "7"
        conn.close()
        assert gw.stats()["gateway_throttled"] == 1.0
        # Free a seat and the next request is admitted again.
        backend.finish(1)
        assert _wait_until(lambda: gw.stats()["gateway_inflight"] == 1.0)
        events = _read_events(open_conns[0][1])
        assert events[-1][0] == "done"
        for conn, _ in open_conns:
            conn.close()

    def test_http_cancel_ends_stream_with_cancelled_event(self,
                                                          dummy_gateway):
        gw, backend = dummy_gateway
        conn, resp = _post(gw.port, "/v1/generate",
                           {"prompt": [1], "stream": True})
        events = _read_events(resp, stop_on_final=False, max_events=1)
        gid = events[0][1]["gid"]
        _wait_until(lambda: backend.has(1))
        backend.feed(1, [42])
        cconn, cresp = _post(gw.port, f"/v1/cancel/{gid}")
        assert cresp.status == 200
        assert json.loads(cresp.read())["cancelled"] is True
        cconn.close()
        events = _read_events(resp)
        conn.close()
        assert backend.cancel_calls == [1]
        kinds = [k for k, _ in events]
        assert kinds[-1] == "done"
        assert events[-1][1]["finish_reason"] == "cancelled"
        # Zero tokens stream after the cancel resolves.
        backend.feed(1, [43])
        assert 43 not in [t for k, d in events if k == "token"
                          for t in d["tokens"]]
        assert gw.stats()["gateway_cancel_requests"] == 1.0

    def test_cancel_unknown_gid_404(self, dummy_gateway):
        gw, _ = dummy_gateway
        conn, resp = _post(gw.port, "/v1/cancel/g-404")
        assert resp.status == 404
        assert json.loads(resp.read())["cancelled"] is False
        conn.close()

    def test_client_disconnect_cancels_backend(self, dummy_gateway):
        """Dropping the socket mid-stream frees the backend slot — the
        same path as an explicit /v1/cancel, minus the courtesy."""
        gw, backend = dummy_gateway
        conn, resp = _post(gw.port, "/v1/generate",
                           {"prompt": [1], "stream": True})
        _read_events(resp, stop_on_final=False, max_events=1)  # start event
        # Drop the socket for real (http.client keeps the fd alive
        # through the response's makefile handle until BOTH close): the
        # writer's next keepalive write then breaks the pipe.
        resp.close()
        conn.close()
        assert _wait_until(lambda: backend.cancel_calls == [1], timeout=30)
        assert _wait_until(
            lambda: gw.stats()["gateway_disconnects"] == 1.0)

    def test_close_drains_open_streams_with_final_event(self):
        """SIGTERM drain: clients see an explicit shutdown event, not a
        dropped socket, and new work is refused."""
        backend = DummyBackend()
        gw = GatewayServer(backend, port=0, max_inflight=4,
                           keepalive_s=0.05)
        conn, resp = _post(gw.port, "/v1/generate",
                           {"prompt": [1], "stream": True})
        _read_events(resp, stop_on_final=False, max_events=1)
        gw.close()
        events = _read_events(resp)
        conn.close()
        assert events[-1][0] == "done"
        assert events[-1][1]["finish_reason"] == "shutdown"
        with pytest.raises(Exception):
            _, resp2 = _post(gw.port, "/v1/generate", {"prompt": [2]})
            assert resp2.status == 503

    def test_max_inflight_validated(self):
        with pytest.raises(ValueError, match="max_inflight"):
            GatewayServer(DummyBackend(), max_inflight=0, start=False)


# ---------------------------------------------------------------------------
# Real engine: parity, cancellation that frees KV, end to end over HTTP
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gpt2_engine(request):
    mesh_dp = request.getfixturevalue("mesh_dp")
    eng = ServeEngine("gpt2", mesh=mesh_dp, preset="tiny")
    yield eng
    eng.close()


def _mixed_requests(vocab, n=8, seed=2):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, size=((4, 6, 9)[i % 3],),
                          dtype=np.int32), (3, 6, 4)[i % 3])
            for i in range(n)]


class _Collector:
    """on_token sink: concatenates batches, flags tokens that arrive
    after its Future resolved cancelled, and marks first delivery."""

    def __init__(self):
        self.tokens = []
        self.first = threading.Event()
        self.after_cancel = 0
        self.future = None

    def __call__(self, toks):
        if self.future is not None and self.future.cancelled():
            self.after_cancel += len(toks)
        self.tokens.extend(int(t) for t in toks)
        self.first.set()


def _streamed_parity(engine, **sched_kw):
    vocab = engine.module.cfg.vocab_size
    reqs = _mixed_requests(vocab)
    with ContinuousScheduler(engine, num_slots=8, max_total_len=32,
                             **sched_kw) as sched:
        cols = [_Collector() for _ in reqs]
        futs = [sched.submit(p, max_new_tokens=m, on_token=c)
                for (p, m), c in zip(reqs, cols)]
        for c, f in zip(cols, futs):
            c.future = f
        outs = [f.result(timeout=300) for f in futs]
        stats = sched.stats()
    for (prompt, horizon), col, out in zip(reqs, cols, outs):
        # THE acceptance property: streaming is delivery, not a
        # different decode — streamed == whole, token for token.
        assert col.tokens == [int(t) for t in out]
        np.testing.assert_array_equal(
            out, fixed_reference(engine, prompt, horizon))
    assert stats["ttfb_p50_ms"] > 0.0
    assert stats["ttfb_p99_ms"] >= stats["ttfb_p50_ms"]
    assert stats["cancelled"] == 0.0


class TestStreamingParity:
    def test_dense_k1_streamed_equals_whole(self, gpt2_engine):
        _streamed_parity(gpt2_engine)

    @pytest.mark.serve_slow
    @pytest.mark.parametrize("cache_mode,megastep,async_decode", [
        ("dense", 4, False),
        ("dense", 1, True),
        ("dense", 4, True),
        ("paged", 1, False),
        ("paged", 4, False),
        ("paged", 1, True),
        ("paged", 4, True),
    ])
    def test_streamed_equals_whole_matrix(self, gpt2_engine, cache_mode,
                                          megastep, async_decode):
        kw = {"megastep": megastep, "async_decode": async_decode}
        if cache_mode == "paged":
            kw.update(cache_mode="paged", block_size=4)
        _streamed_parity(gpt2_engine, **kw)

    def test_on_token_must_be_callable(self, gpt2_engine):
        sched = ContinuousScheduler(gpt2_engine, num_slots=8,
                                    max_total_len=16, start=False)
        with pytest.raises(TypeError, match="on_token"):
            sched.submit(np.zeros((2,), np.int32), max_new_tokens=2,
                         on_token="nope")
        sched.close(timeout=0.1)


class TestCancellation:
    def test_queued_cancel_never_touches_a_slot(self, gpt2_engine):
        """Unstarted loop: the request is still queued, so cancel sheds
        it synchronously and the Future resolves cancelled."""
        sched = ContinuousScheduler(gpt2_engine, num_slots=8,
                                    max_total_len=16, start=False)
        fut = sched.submit(np.zeros((4,), np.int32), max_new_tokens=4)
        assert sched.cancel(fut.rid) is True
        assert fut.cancelled()
        with pytest.raises(CancelledError):
            fut.result(timeout=1)
        assert sched.stats()["cancelled"] == 1.0
        assert sched.cancel(fut.rid) is False  # already gone
        sched.close(timeout=0.1)

    def test_mid_decode_cancel_frees_kv_blocks(self, gpt2_engine):
        """The PR's bugfix regression: cancel mid-decode retires the slot
        at the next iteration boundary, blocks_in_use returns to
        baseline (the request does NOT decode to max_new_tokens), and
        ZERO further tokens stream."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(5)
        with ContinuousScheduler(gpt2_engine, num_slots=8,
                                 max_total_len=32, cache_mode="paged",
                                 block_size=4) as sched:
            baseline = sched.stats()["blocks_in_use"]
            keep_p = rng.integers(0, vocab, size=(5,), dtype=np.int32)
            cancel_p = rng.integers(0, vocab, size=(6,), dtype=np.int32)
            col = _Collector()
            keep_f = sched.submit(keep_p, max_new_tokens=4)
            cancel_f = sched.submit(cancel_p, max_new_tokens=24,
                                    on_token=col)
            col.future = cancel_f
            assert col.first.wait(timeout=120)  # mid-decode now
            assert sched.cancel(cancel_f.rid) is True
            with pytest.raises(CancelledError):
                cancel_f.result(timeout=120)
            streamed_at_cancel = len(col.tokens)
            # The co-resident request is untouched by the neighbour's
            # cancellation.
            np.testing.assert_array_equal(
                keep_f.result(timeout=300),
                fixed_reference(gpt2_engine, keep_p, 4))
            assert _wait_until(
                lambda: sched.stats()["blocks_in_use"] == baseline,
                timeout=60)
            time.sleep(0.2)  # a zombie emit would land within a step
            assert col.after_cancel == 0
            assert len(col.tokens) == streamed_at_cancel < 24
            assert sched.stats()["cancelled"] == 1.0

    @pytest.mark.serve_slow
    def test_mid_prefill_cancel_frees_kv_blocks(self, gpt2_engine):
        """Chunked prefill: cancelling while the prompt is still
        prefilling in budgeted chunks gives the blocks AND the backlog
        bookkeeping back."""
        vocab = gpt2_engine.module.cfg.vocab_size
        prompt = (np.arange(24, dtype=np.int32) * 7 + 3) % vocab
        with ContinuousScheduler(gpt2_engine, num_slots=8,
                                 max_total_len=32, cache_mode="paged",
                                 block_size=4, prefill_budget=1) as sched:
            baseline = sched.stats()["blocks_in_use"]
            col = _Collector()
            fut = sched.submit(prompt, max_new_tokens=4, on_token=col)
            col.future = fut
            assert _wait_until(
                lambda: sched.stats()["prefilling_slots"] > 0, timeout=120,
                interval=0.0005)
            assert sched.cancel(fut.rid) is True
            with pytest.raises(CancelledError):
                fut.result(timeout=120)
            assert _wait_until(
                lambda: sched.stats()["blocks_in_use"] == baseline,
                timeout=60)
            s = sched.stats()
            assert s["prefilling_slots"] == 0.0
            assert s["prefill_backlog_tokens"] == 0.0
            # The freed slot still serves the next request correctly.
            nxt = sched.submit(prompt[:6], max_new_tokens=3)
            np.testing.assert_array_equal(
                nxt.result(timeout=300),
                fixed_reference(gpt2_engine, prompt[:6], 3))

    @pytest.mark.serve_slow
    def test_mid_megastep_cancel(self, gpt2_engine):
        """Cancel between megastep fetches: the in-flight launch is
        flushed, the slot retires, and the stream stops cold."""
        vocab = gpt2_engine.module.cfg.vocab_size
        prompt = (np.arange(5, dtype=np.int32) * 11 + 1) % vocab
        with ContinuousScheduler(gpt2_engine, num_slots=8,
                                 max_total_len=32, megastep=4,
                                 async_decode=True) as sched:
            col = _Collector()
            fut = sched.submit(prompt, max_new_tokens=24, on_token=col)
            col.future = fut
            assert col.first.wait(timeout=120)
            assert sched.cancel(fut.rid) is True
            with pytest.raises(CancelledError):
                fut.result(timeout=120)
            n = len(col.tokens)
            time.sleep(0.3)
            assert col.after_cancel == 0
            assert len(col.tokens) == n < 24


@pytest.fixture(scope="module")
def live_gateway(gpt2_engine):
    """GatewayServer over the real continuous path, batcher-fronted the
    way serve.py wires it: gateway -> DynamicBatcher -> scheduler."""
    sched = ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=32)
    batcher = DynamicBatcher(iteration_level=True, scheduler=sched)
    gw = GatewayServer(batcher, port=0, max_inflight=8, keepalive_s=0.2)
    yield gw, gpt2_engine
    gw.close()
    batcher.close()


class TestGatewayEndToEnd:
    def test_streamed_tokens_matchfixed_reference(self, live_gateway):
        gw, engine = live_gateway
        vocab = engine.module.cfg.vocab_size
        prompt = [int(t) for t in (np.arange(6) * 5 + 2) % vocab]
        conn, resp = _post(gw.port, "/v1/generate",
                           {"prompt": prompt, "max_new_tokens": 5,
                            "stream": True}, timeout=300)
        assert resp.status == 200
        events = _read_events(resp)
        conn.close()
        toks = [t for kind, d in events if kind == "token"
                for t in d["tokens"]]
        ref = fixed_reference(engine, np.asarray(prompt, np.int32), 5)
        assert toks == [int(t) for t in ref]
        assert events[-1][0] == "done"
        assert events[-1][1]["finish_reason"] == "length"
        assert events[-1][1]["tokens_streamed"] == 5

    def test_whole_response_matches_streamed(self, live_gateway):
        gw, engine = live_gateway
        vocab = engine.module.cfg.vocab_size
        prompt = [int(t) for t in (np.arange(4) * 3 + 1) % vocab]
        conn, resp = _post(gw.port, "/v1/generate",
                           {"prompt": prompt, "max_new_tokens": 4},
                           timeout=300)
        body = json.loads(resp.read())
        conn.close()
        ref = fixed_reference(engine, np.asarray(prompt, np.int32), 4)
        assert body["tokens"] == [int(t) for t in ref]

    def test_http_cancel_stops_generation_early(self, live_gateway):
        """End to end: /v1/cancel mid-decode answers a ``cancelled``
        final event with fewer tokens than the horizon."""
        gw, engine = live_gateway
        vocab = engine.module.cfg.vocab_size
        prompt = [int(t) for t in (np.arange(5) * 9 + 4) % vocab]
        conn, resp = _post(gw.port, "/v1/generate",
                           {"prompt": prompt, "max_new_tokens": 24,
                            "stream": True}, timeout=300)
        events = _read_events(resp, stop_on_final=False, max_events=1)
        gid = events[0][1]["gid"]
        # Wait for the first token so the cancel lands mid-decode.
        first = _read_events(resp, stop_on_final=False, max_events=1)
        assert first and first[0][0] == "token"
        cconn, cresp = _post(gw.port, f"/v1/cancel/{gid}", timeout=300)
        assert json.loads(cresp.read())["cancelled"] is True
        cconn.close()
        tail = _read_events(resp)
        conn.close()
        assert tail[-1][0] == "done"
        assert tail[-1][1]["finish_reason"] == "cancelled"
        streamed = sum(len(d["tokens"]) for k, d in first + tail
                       if k == "token")
        assert tail[-1][1]["tokens_streamed"] < 24
        assert streamed < 24
