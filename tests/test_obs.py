"""Observability tests: metrics registry, exporters, tracing, writers."""

import glob
import json
import logging
import os
import threading
import time
import timeit
import urllib.request

import pytest

from distributed_tensorflow_tpu.obs import (
    MetricsFileWriter,
    MetricsServer,
    Profile,
    Registry,
    TensorBoardHook,
    Tracer,
    render_prometheus,
)
from distributed_tensorflow_tpu.obs.trace import now
from distributed_tensorflow_tpu.training import FP32, TrainLoop, make_train_step
from tests.test_training import linear_batch, make_linear_state, quadratic_loss


def run_loop(hooks, steps=12):
    state = make_linear_state()
    step = make_train_step(quadratic_loss, precision=FP32)
    data = iter(lambda: linear_batch(), None)
    loop = TrainLoop(step, state, data, hooks=hooks, metrics_every=2)
    loop.run(steps)


class TestTensorBoardHook:
    def test_writes_event_files(self, tmp_path):
        d = str(tmp_path / "tb")
        run_loop([TensorBoardHook(d, every_steps=2)])
        files = os.listdir(d)
        assert any("tfevents" in f for f in files), files


class TestMetricsFileWriter:
    def test_writes_parseable_jsonl(self, tmp_path):
        p = str(tmp_path / "metrics.jsonl")
        run_loop([MetricsFileWriter(p)])
        lines = [json.loads(l) for l in open(p)]
        assert lines, "no metrics written"
        assert all("step" in l and "loss" in l for l in lines)
        steps = [l["step"] for l in lines]
        assert steps == sorted(steps)


class TestEvalReachesWriters:
    def test_eval_points_written_to_jsonl_and_tb(self, tmp_path):
        from distributed_tensorflow_tpu.train_lib import TrainArgs, run

        tb = str(tmp_path / "tb")
        jl = str(tmp_path / "m.jsonl")
        run(TrainArgs(
            model="mnist", steps=20, batch_size=32, log_every=10,
            eval_every=10, eval_batches=2,
            tensorboard_dir=tb, metrics_file=jl,
        ))
        lines = [json.loads(l) for l in open(jl)]
        eval_lines = [l for l in lines if any(k.startswith("eval_")
                                             for k in l)]
        assert eval_lines, "no eval metrics in JSONL"
        assert os.listdir(tb)


class TestStartProfilerServer:
    def test_second_call_with_different_port_warns(self, monkeypatch, caplog):
        import logging

        from distributed_tensorflow_tpu.obs import profiling as prof

        started = []
        monkeypatch.setattr(prof, "_SERVER", None)
        monkeypatch.setattr(prof, "_PORT", None)
        monkeypatch.setattr(
            prof.jax.profiler, "start_server",
            lambda port: started.append(port) or object())

        with caplog.at_level(logging.INFO, logger=prof.__name__):
            h1 = prof.start_profiler_server(9012)
            h2 = prof.start_profiler_server(9012)  # same port: silent no-op
            warnings = [r for r in caplog.records
                        if r.levelno == logging.WARNING]
            assert h2 is h1 and not warnings
            h3 = prof.start_profiler_server(9999)  # conflicting port
        assert h3 is h1
        assert started == [9012], "server must only ever start once"
        warnings = [r for r in caplog.records if r.levelno == logging.WARNING]
        assert len(warnings) == 1
        # The warning names BOTH the live port and the ignored request.
        assert "9012" in warnings[0].getMessage()
        assert "9999" in warnings[0].getMessage()


class TestProfile:
    def test_trace_context_manager(self, tmp_path):
        import jax
        import jax.numpy as jnp

        d = str(tmp_path / "prof")
        with Profile(d):
            jax.jit(lambda x: x * 2)(jnp.ones((8,))).block_until_ready()
        found = []
        for root, _, files in os.walk(d):
            found += [f for f in files if f.endswith((".pb", ".json.gz",
                                                      ".xplane.pb"))]
        assert found, f"no trace artifacts under {d}"


# -- metrics registry ---------------------------------------------------------


class TestRegistry:
    def test_get_or_create_returns_same_family(self):
        r = Registry()
        c1 = r.counter("dtt_x_total", "help")
        c2 = r.counter("dtt_x_total")
        assert c1 is c2
        c1.inc(3)
        assert c2.value == 3

    def test_type_conflict_raises(self):
        r = Registry()
        r.counter("dtt_x_total")
        with pytest.raises(ValueError, match="already registered"):
            r.gauge("dtt_x_total")

    def test_labelnames_conflict_raises(self):
        r = Registry()
        r.counter("dtt_x_total", labelnames=("kind",))
        with pytest.raises(ValueError, match="labels"):
            r.counter("dtt_x_total", labelnames=("other",))

    def test_counter_rejects_negative(self):
        r = Registry()
        with pytest.raises(ValueError, match="only go up"):
            r.counter("dtt_x_total").inc(-1)

    def test_labels_key_children_independently(self):
        r = Registry()
        c = r.counter("dtt_compiles_total", labelnames=("kind",))
        c.labels(kind="prefill").inc()
        c.labels(kind="decode").inc(2)
        c.labels(kind="prefill").inc()
        values = {k: child.value for k, child in c.samples()}
        assert values == {("decode",): 2, ("prefill",): 2}
        # A labeled family refuses unlabeled use.
        with pytest.raises(ValueError, match="use .labels"):
            c.inc()

    def test_gauge_set_inc_dec(self):
        r = Registry()
        g = r.gauge("dtt_depth")
        g.set(5)
        g.inc()
        g.dec(2)
        assert g.value == 4

    def test_histogram_quantiles_interpolate(self):
        r = Registry()
        h = r.histogram("dtt_lat_seconds", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.6, 5.0):
            h.observe(v)
        assert h.count == 4
        assert h.sum == pytest.approx(6.15)
        # p50 lands in the (0.1, 1.0] bucket, interpolated.
        assert 0.1 < h.quantile(0.5) <= 1.0
        # The +Inf bucket reports its finite lower edge.
        h.observe(99.0)
        assert h.quantile(1.0) == 10.0

    def test_thread_safety_smoke(self):
        r = Registry()
        c = r.counter("dtt_races_total")
        h = r.histogram("dtt_race_seconds", buckets=(0.5,))

        def work():
            for _ in range(1000):
                c.inc()
                h.observe(0.25)

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 8000
        assert h.count == 8000

    def test_stats_provider_bridge_uniquifies(self):
        r = Registry()
        ns1 = r.register_stats("serve/x", lambda: {"a": 1})
        ns2 = r.register_stats("serve/x", lambda: {"a": 2})
        assert ns1 == "serve/x" and ns2 == "serve/x-2"
        assert r.stats(ns2) == {"a": 2}
        r.unregister_stats(ns1)
        assert r.stats(ns1) is None


class TestPrometheusRendering:
    def test_text_format(self):
        r = Registry()
        r.counter("dtt_req_total", "requests").inc(3)
        r.gauge("dtt_depth", "queue depth", labelnames=("pool",)) \
            .labels(pool="a").set(2)
        h = r.histogram("dtt_lat_seconds", "latency", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 3.0):
            h.observe(v)
        text = render_prometheus(r)
        assert text.endswith("\n")
        lines = text.splitlines()
        assert "# TYPE dtt_req_total counter" in lines
        assert "dtt_req_total 3" in lines
        assert "# HELP dtt_depth queue depth" in lines
        assert 'dtt_depth{pool="a"} 2' in lines
        # Histogram: cumulative buckets + sum + count.
        assert 'dtt_lat_seconds_bucket{le="0.1"} 1' in lines
        assert 'dtt_lat_seconds_bucket{le="1"} 2' in lines
        assert 'dtt_lat_seconds_bucket{le="+Inf"} 3' in lines
        assert "dtt_lat_seconds_sum 3.55" in lines
        assert "dtt_lat_seconds_count 3" in lines

    def test_scrape_endpoint_round_trip(self):
        r = Registry()
        r.counter("dtt_scraped_total").inc()
        with MetricsServer(port=0, registry=r, host="127.0.0.1") as srv:
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=5
            ).read().decode()
        assert "dtt_scraped_total 1" in body


# -- tracing ------------------------------------------------------------------


class TestTracer:
    def test_disabled_tracer_records_nothing(self):
        """No profiler session and not enabled: nothing is appended,
        whichever way a span is emitted."""
        t = Tracer()
        t.add_span("x", start=0.0, end=1.0)
        t.add_instant("y")
        t.add_flow("request", id=1, phase="s")
        with t.span("z", cat="serve") as open_span:
            open_span.set(k=1)
        assert len(t) == 0 and not t.recording and t.spans() == []

    def test_records_under_a_profiler_session_without_enable(self, tmp_path):
        """Any ``jax.profiler`` session switches the ring on; the
        context-managed spans also enter the profiler's own trace under
        ``dtt/<cat>/<name>``, the after-the-fact ones stay ring-only."""
        import jax
        from jax.profiler import ProfileData

        t = Tracer()
        with t.span("before", cat="train"):
            pass
        run = t.span("run", cat="train")     # open when the session begins
        run.__enter__()
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert t.recording and not t.enabled
            with t.span("step", cat="train"):
                with t.span("dispatch", cat="train"):
                    pass
            run.__exit__(None, None, None)
            t.add_span("queue_wait", cat="serve", tid=3,
                       start=now() - 1.0, end=now())
            late = t.span("hooks", cat="train")
            late.__enter__()                 # still open when it ends
        finally:
            jax.profiler.stop_trace()
        late.__exit__(None, None, None)
        with t.span("after", cat="train"):
            pass
        # What opens or closes inside the session is recorded; only what
        # did both is in the profiler's own trace.
        assert [s[0] for s in t.spans()] == [
            "dtt/train/dispatch", "dtt/train/step", "dtt/train/run",
            "dtt/serve/queue_wait", "dtt/train/hooks"]
        (path,) = glob.glob(str(
            tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
        in_xplane = {e.name for plane in ProfileData.from_file(path).planes
                     for line in plane.lines for e in line.events
                     if e.name.startswith("dtt/")}
        assert in_xplane == {"dtt/train/step", "dtt/train/dispatch"}

    def test_spans_are_perf_counter_seconds_and_name_their_parent(self):
        t = Tracer(enabled=True)
        before = time.perf_counter()
        with t.span("step", cat="train", args={"n": 1}) as step:
            with t.span("dispatch", cat="train"):
                pass
            step.set(late=True)
        t.add_instant("mark", cat="train")           # not a span
        after = time.perf_counter()
        (name, start, end, tid, args), = t.spans(name="dtt/train/step")
        assert name == "dtt/train/step" and tid == 0
        assert before <= start <= end <= after
        assert args["n"] == 1 and args["late"] is True
        (_, c_start, c_end, _, c_args), = t.spans(name="dtt/train/dispatch")
        assert c_args["parent"] == args["span_id"] and "parent" not in args
        assert start <= c_start <= c_end <= end
        assert len(t.spans(cat="train")) == 2 and t.spans(cat="serve") == []
        # The Chrome rendering is the same interval in microseconds.
        ev = next(e for e in t.events() if e["name"] == "step")
        assert ev["dur"] == pytest.approx((end - start) * 1e6, abs=2)

    def test_a_span_costs_next_to_nothing_while_nothing_records(self):
        """A scheduler iteration of the saturated serving cell opens seven
        spans and a training step four or five (counted in traced runs):
        at eight spans both stay under 20 us of instrumentation (best of
        five rounds of 10,000, so that a busy test machine does not
        decide)."""
        t = Tracer()

        def one():
            with t.span("iteration", cat="serve"):
                pass

        per_span = min(timeit.repeat(one, number=10_000, repeat=5)) / 10_000
        assert len(t) == 0
        assert 8 * per_span < 20e-6, f"{per_span * 1e9:.0f} ns a span"

    @pytest.mark.parametrize("cat", ["startup", "compile"])
    def test_two_categories_are_recorded_whatever_records(self, cat):
        """Set-up's phases and the compiles land in the ring with the
        tracer off and no profiler session open, by either way of emitting
        a span; the loop's categories still do not."""
        t = Tracer()
        assert not t.recording
        with t.span("phase", cat=cat, args={"model": "gpt2"}):
            with t.span("iteration", cat="serve"):
                pass
            with t.span("step", cat="train"):
                pass
        t.add_span("backend", cat=cat, start=1.0, end=3.0)
        t.add_span("queue_wait", cat="serve", start=1.0, end=3.0)
        assert [s[0] for s in t.spans()] == [
            f"dtt/{cat}/phase", f"dtt/{cat}/backend"]
        assert t.spans(cat=cat)[0][4]["model"] == "gpt2"
        assert t.spans(cat="serve") == [] and t.spans(cat="train") == []

    def test_add_span_names_the_span_it_fell_in(self):
        """A span recorded after the fact carries as ``parent`` the span
        open on the calling thread (recorded or not), and none outside one
        or from a thread that has none open."""
        t = Tracer()
        t.add_span("backend", cat="compile", start=0.0, end=1.0)
        with t.span("iteration", cat="serve"):       # not recorded: off
            with t.span("program_first_launch", cat="startup") as _:
                t.add_span("backend", cat="compile", start=1.0, end=2.0,
                           args={"program": "jit_step"})
                other = threading.Thread(target=lambda: t.add_span(
                    "backend", cat="compile", start=2.0, end=3.0))
                other.start()
                other.join(timeout=10)
            t.add_span("lower", cat="compile", start=3.0, end=4.0)
        outside, inside, elsewhere = t.spans(name="dtt/compile/backend")
        (launch,) = t.spans(name="dtt/startup/program_first_launch")
        assert "parent" not in outside[4] and "parent" not in elsewhere[4]
        assert inside[4] == {"program": "jit_step",
                             "parent": launch[4]["span_id"]}
        # The unrecorded loop span's id: a child still says it had one.
        (lower,) = t.spans(name="dtt/compile/lower")
        assert lower[4]["parent"] == launch[4]["parent"]

    def test_spanned_runs_each_call_inside_a_span(self, monkeypatch):
        from distributed_tensorflow_tpu.obs import trace as obs_trace

        t = Tracer()
        monkeypatch.setattr(obs_trace, "_default_tracer", t)

        class Engine:
            @obs_trace.spanned("engine_init", "startup")
            def __init__(self, width, *, depth=2):
                """Doc kept."""
                self.size = width * depth
                with t.span("params_placed", cat="startup"):
                    pass

        assert Engine(3, depth=4).size == 12
        assert Engine.__init__.__doc__ == "Doc kept."
        child, parent = t.spans(cat="startup")
        assert parent[0] == "dtt/startup/engine_init"
        assert child[4]["parent"] == parent[4]["span_id"]
        with pytest.raises(TypeError):
            Engine()                                  # the span still closes
        assert not t._local.stack

    def test_ring_buffer_bounds_memory(self):
        t = Tracer(capacity=4, enabled=True)
        for i in range(10):
            t.add_span(f"s{i}", start=float(i), end=float(i) + 0.5)
        assert len(t) == 4
        assert [e["name"] for e in t.events()] == ["s6", "s7", "s8", "s9"]

    def test_chrome_trace_schema(self, tmp_path):
        t = Tracer(enabled=True)
        with t.span("prefill", cat="serve", tid=7, args={"rid": 7}):
            pass
        t.add_instant("retire", cat="serve", tid=7)
        path = str(tmp_path / "trace.json")
        assert t.write(path) == 2
        doc = json.load(open(path))
        evs = doc["traceEvents"]
        # Metadata event first, then the recorded events.
        assert evs[0]["ph"] == "M" and evs[0]["name"] == "process_name"
        span = next(e for e in evs if e["name"] == "prefill")
        assert span["ph"] == "X" and span["tid"] == 7
        assert isinstance(span["ts"], int) and isinstance(span["dur"], int)
        assert span["args"]["rid"] == 7 and "span_id" in span["args"]
        instant = next(e for e in evs if e["name"] == "retire")
        assert instant["ph"] == "i"


# -- monitor hooks as thin registry readers ----------------------------------


FIXED_STATS = {
    "queue_depth": 3, "capacity": 64, "completed": 10, "rejected": 1,
    "batches": 4, "avg_batch_occupancy": 2.5,
    "p50_latency_ms": 12.0, "p99_latency_ms": 40.0,
}

CONTINUOUS_STATS = {
    "queue_depth": 2, "capacity": 64, "completed": 9, "rejected": 0,
    "iterations": 30, "active_slots": 4, "num_slots": 8,
    "slot_occupancy": 0.5, "admissions_per_iter": 0.3,
    "retirements_per_iter": 0.3, "ttft_p50_ms": 20.0, "ttft_p99_ms": 50.0,
    "tpot_mean_ms": 1.5, "p50_latency_ms": 30.0, "p99_latency_ms": 80.0,
}


class TestStartupReport:
    """``obs/startup.py``: the ``startup`` line and its gauge, from spans
    the ring holds."""

    def _filled(self):
        t = Tracer()
        with t.span("build_step", cat="startup") as phase:
            with t.span("abstract_state", cat="startup"):
                t.add_span("trace", cat="compile", start=now() - 0.5,
                           end=now(), args={"program": "jit_init_fn"})
            phase.set(grad_reduce="none")
        for kind in ("slot_prefill", "slot_prefill", "slot_megastep"):
            with t.span("program_first_launch", cat="startup",
                        args={"kind": kind}):
                pass
        t.add_span("backend", cat="compile", start=10.0, end=12.0,
                   args={"program": "jit_step", "cache": "miss"})
        t.add_span("backend", cat="compile", start=20.0, end=21.0,
                   args={"program": "jit_step", "cache": "hit"})
        t.add_span("lower", cat="compile", start=9.0, end=10.0,
                   args={"program": "jit_step"})
        return t

    def test_summary_names_children_and_programs(self):
        from distributed_tensorflow_tpu.obs import startup

        said = startup.summary(self._filled())
        assert set(said["phases"]) == {
            "build_step", "build_step/abstract_state",
            "program_first_launch[slot_prefill]",
            "program_first_launch[slot_megastep]"}
        assert said["programs"]["jit_step"] == {
            "backend_s": pytest.approx(3.0), "lower_s": pytest.approx(1.0),
            "cache": {"miss": 1, "hit": 1}}
        assert said["programs"]["jit_init_fn"] == {
            "trace_s": pytest.approx(0.5, abs=0.01)}
        # The union: 9-12 and 20-21 for jit_step, and the half second of
        # the trace, which the phases round its end do not add to.
        assert said["covered_s"] == pytest.approx(4.5, abs=0.01)
        assert startup.union_seconds(
            [(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == pytest.approx(4.0)

    def test_summary_keeps_what_a_phase_said_of_itself(self):
        from distributed_tensorflow_tpu.obs import startup

        t = self._filled()
        with t.span("engine_init", cat="startup"):
            with t.span("params_placed", cat="startup",
                        args={"restored": False}):
                t.add_span("params_cast", cat="startup", start=now(),
                           end=now(), args={"leaves_cast": 10})
        # Not ``kind`` (it is in the phase's name), ``span_id``, ``parent``.
        assert startup.summary(t)["phase_args"] == {
            "build_step": {"grad_reduce": "none"},
            "engine_init/params_placed": {"restored": False},
            "engine_init/params_placed/params_cast": {"leaves_cast": 10}}

    def test_report_logs_one_line_and_sets_the_gauge(self, caplog):
        from distributed_tensorflow_tpu.obs import startup

        reg = Registry()
        with caplog.at_level(logging.INFO,
                             logger="distributed_tensorflow_tpu.obs.startup"):
            said = startup.report(self._filled(), reg)
        (line,) = [r.getMessage() for r in caplog.records
                   if r.getMessage().startswith("startup ")]
        assert json.loads(line[len("startup "):]) == json.loads(
            json.dumps(said))
        text = render_prometheus(reg)
        assert 'dtt_startup_seconds{phase="build_step/abstract_state"}' in text
        assert 'phase="program_first_launch[slot_prefill]"' in text

    def test_hook_reports_once_when_the_first_loss_lands(self, monkeypatch):
        from distributed_tensorflow_tpu.obs import startup

        calls = []
        monkeypatch.setattr(startup, "report", lambda: calls.append(1) or {})
        run_loop([startup.StartupReportHook()], steps=8)
        assert calls == [1]


class TestHookLogCompat:
    """The refactor to registry readers must not change one log byte."""

    def _log_line(self, caplog, stats):
        from distributed_tensorflow_tpu.obs import serve as obs_serve

        r = Registry()
        ns = r.register_stats("serve/test", lambda: dict(stats))
        hook = obs_serve.ServeMonitorHook(ns, registry=r)
        with caplog.at_level(logging.INFO, logger=obs_serve.__name__):
            m = hook.log(100)
        assert m["serve_completed"] == stats["completed"]
        return caplog.records[-1].getMessage()

    def test_fixed_mode_line_unchanged(self, caplog):
        assert self._log_line(caplog, FIXED_STATS) == (
            "serve @ 100: depth=3/64 done=10 rej=1 batches=4 "
            "occupancy=2.50 p50=12.0ms p99=40.0ms")

    def test_continuous_mode_line_unchanged(self, caplog):
        assert self._log_line(caplog, CONTINUOUS_STATS) == (
            "serve @ 100: depth=2/64 done=9 rej=0 iters=30 slots=4/8 "
            "occupancy=0.50 adm/it=0.30 ret/it=0.30 ttft_p50=20.0ms "
            "ttft_p99=50.0ms tpot=1.50ms p50=30.0ms p99=80.0ms")

    def test_spec_line_pinned(self, caplog):
        """A spec-enabled scheduler gets its OWN pinned line after the
        continuous one; spec-off stats (no spec_k key, or spec_k=0) must
        not emit it — the continuous line above stays byte-identical."""
        stats = dict(CONTINUOUS_STATS, spec_k=4, spec_drafted=40,
                     spec_accepted=25, spec_acceptance_rate=0.625,
                     spec_launches=12, spec_emitted=37,
                     spec_tokens_per_launch=37 / 12)
        assert self._log_line(caplog, stats) == (
            "serve @ 100: spec k=4 drafted=40 accepted=25 "
            "accept_rate=0.62 launches=12 emitted=37 tok/launch=3.08")
        spec_lines = [rec.getMessage() for rec in caplog.records
                      if "spec k=" in rec.getMessage()]
        assert len(spec_lines) == 1
        caplog.clear()
        self._log_line(caplog, dict(CONTINUOUS_STATS, spec_k=0))
        assert not any("spec k=" in rec.getMessage()
                       for rec in caplog.records)

    def test_prefetch_line_unchanged(self, caplog):
        from distributed_tensorflow_tpu.obs import prefetch as obs_prefetch

        r = Registry()
        ns = r.register_stats("prefetch", lambda: {
            "queue_depth": 2, "capacity": 2, "enqueued": 50, "dequeued": 48,
            "producer_wait_s": 0.125, "consumer_wait_s": 0.5,
        })
        hook = obs_prefetch.PrefetchMonitorHook(ns, every_steps=1, registry=r)

        class FakeLoop:
            last_logged_metrics = {}

        with caplog.at_level(logging.INFO, logger=obs_prefetch.__name__):
            hook.after_step(FakeLoop(), 100, {})
        assert caplog.records[-1].getMessage() == (
            "prefetch @ step 100: depth=2/2 in=50 out=48 "
            "producer_wait=0.125s consumer_wait=0.500s")

    def test_hook_resolves_component_via_registry_namespace(self):
        """Passing the component resolves the provider registered under its
        obs_namespace — the hook never calls a private stats path."""
        from distributed_tensorflow_tpu.obs.serve import ServeMonitorHook

        r = Registry()

        class FakeBatcher:
            obs_namespace = None

            def stats(self):  # the legacy escape hatch, NOT used here
                raise AssertionError("hook must read the registry provider")

        b = FakeBatcher()
        b.obs_namespace = r.register_stats(
            "serve/fake", lambda: dict(FIXED_STATS))
        hook = ServeMonitorHook(b, registry=r)
        assert hook.metrics()["serve_queue_depth"] == 3


class TestInstrumentedComponents:
    def test_train_loop_publishes_step_metrics(self):
        from distributed_tensorflow_tpu.obs import default_registry

        r = default_registry()
        steps = r.counter("dtt_train_steps_total")
        before = steps.value
        run_loop([], steps=6)
        assert steps.value == before + 6
        assert r.histogram("dtt_train_step_seconds").count >= 6

    def test_checkpoint_save_restore_metrics_and_spans(self, tmp_path):
        import jax

        from distributed_tensorflow_tpu.checkpoint import CheckpointManager
        from distributed_tensorflow_tpu.obs import (default_registry,
                                                    default_tracer)

        tracer = default_tracer()
        was_enabled = tracer.enabled
        tracer.enable()
        r = default_registry()
        saves = r.histogram("dtt_checkpoint_save_seconds")
        n0 = saves.count
        try:
            state = {"w": jax.numpy.ones((4,))}
            with CheckpointManager(str(tmp_path / "ckpt"),
                                   async_save=False) as mgr:
                mgr.save(1, state, force=True)
                mgr.wait_until_finished()
                restored = mgr.restore(1, template=state)
            assert saves.count == n0 + 1
            names = [e["name"] for e in tracer.events()]
            assert "checkpoint_save" in names
            assert "checkpoint_restore" in names
        finally:
            if not was_enabled:
                tracer.disable()
        assert float(restored["w"][0]) == 1.0


# -- lifecycle attribution ----------------------------------------------------


class TestLifecycleRecorder:
    """The fold is an EXACT partition: phases sum to wall for every
    event path the scheduler can emit (plain, preempt/swap/resume,
    never-admitted, cancelled)."""

    def _rec(self, **kw):
        from distributed_tensorflow_tpu.obs.lifecycle import (
            LifecycleRecorder,
        )

        return LifecycleRecorder(registry=Registry(), **kw)

    def test_stats_keys_match_empty_surface(self):
        from distributed_tensorflow_tpu.obs.lifecycle import (
            EMPTY_LIFECYCLE_STATS,
        )

        rec = self._rec()
        assert set(rec.stats()) == set(EMPTY_LIFECYCLE_STATS)
        assert rec.stats()["lifecycle_enabled"] == 1.0
        assert EMPTY_LIFECYCLE_STATS["lifecycle_enabled"] == 0.0

    def test_plain_request_partition_is_exact(self):
        rec = self._rec()
        rec.record(1, "SUBMIT", t=0.0, prompt_len=8)
        rec.record(1, "QUEUED", t=0.0, depth=1)
        rec.record(1, "ADMITTED", t=1.0, slot=0)
        rec.record(1, "FIRST_TOKEN", t=1.5, chunks=1)
        rec.record(1, "TOKEN_STREAMED", t=2.0, n=1,
                   dispatch_t=1.6, wait_s=0.1)
        rec.record(1, "RETIRED", t=2.25, tokens=2)
        (b,) = rec.breakdowns()
        assert b["queue_wait"] == pytest.approx(1.0)
        assert b["prefill"] == pytest.approx(0.5)
        # gap 0.5: launch in flight 0.4 (0.1 of it blocked on the fetch
        # thread), 0.1 host gap + 0.25 retire tail = stall 0.35.
        assert b["fetch_wait"] == pytest.approx(0.1)
        assert b["decode_compute"] == pytest.approx(0.3)
        assert b["scheduler_stall"] == pytest.approx(0.35)
        assert b["swap"] == 0.0
        assert b["wall"] == pytest.approx(2.25)
        phases = sum(b[p] for p in ("queue_wait", "prefill",
                                    "decode_compute", "fetch_wait",
                                    "swap", "scheduler_stall"))
        assert phases == pytest.approx(b["wall"])
        assert rec.stats()["breakdown_sum_to_wall_ratio"] == \
            pytest.approx(1.0)

    def test_preempt_swap_resume_window(self):
        rec = self._rec()
        rec.record(2, "SUBMIT", t=0.0)
        rec.record(2, "ADMITTED", t=1.0, slot=1)
        rec.record(2, "FIRST_TOKEN", t=1.2)
        rec.record(2, "PREEMPTED", t=1.5, path="swap")
        rec.record(2, "SWAPPED_OUT", t=1.5, swap_bytes=4096)
        rec.record(2, "SWAPPED_IN", t=2.4, swap_bytes=4096)
        rec.record(2, "RESUMED", t=2.5, path="swap")
        rec.record(2, "TOKEN_STREAMED", t=2.75, n=1, dispatch_t=2.55)
        rec.record(2, "RETIRED", t=2.8)
        (b,) = rec.breakdowns()
        assert b["swap"] == pytest.approx(1.0)     # parked 1.5 -> 2.5
        assert b["queue_wait"] == pytest.approx(1.0)
        assert b["prefill"] == pytest.approx(0.2)
        assert b["decode_compute"] == pytest.approx(0.2)
        # eviction slice 0.3 + post-resume host gap 0.05 + tail 0.05
        assert b["scheduler_stall"] == pytest.approx(0.4)
        phases = sum(b[p] for p in ("queue_wait", "prefill",
                                    "decode_compute", "fetch_wait",
                                    "swap", "scheduler_stall"))
        assert phases == pytest.approx(b["wall"]) == pytest.approx(2.8)
        s = rec.stats()
        assert s["ttft_breakdown_queue_wait_p99_ms"] == \
            pytest.approx(1000.0)
        assert s["ttft_breakdown_prefill_p99_ms"] == pytest.approx(200.0)

    def test_recompute_readmission_closes_park(self):
        rec = self._rec()
        rec.record(3, "SUBMIT", t=0.0)
        rec.record(3, "ADMITTED", t=0.5)
        rec.record(3, "FIRST_TOKEN", t=0.7)
        rec.record(3, "PREEMPTED", t=1.0, path="recompute")
        rec.record(3, "ADMITTED", t=2.0, readmission=1)
        rec.record(3, "RETIRED", t=2.1)
        (b,) = rec.breakdowns()
        assert b["swap"] == pytest.approx(1.0)     # parked 1.0 -> 2.0
        assert b["queue_wait"] == pytest.approx(0.5)

    def test_never_admitted_is_all_queue_wait(self):
        rec = self._rec()
        rec.record(4, "SUBMIT", t=0.0)
        rec.record(4, "QUEUED", t=0.0, depth=9)
        rec.record(4, "RETIRED", t=3.0)
        (b,) = rec.breakdowns()
        assert b["queue_wait"] == pytest.approx(3.0)
        assert b["wall"] == pytest.approx(3.0)

    def test_cancelled_excluded_from_aggregates(self):
        rec = self._rec()
        rec.record(5, "SUBMIT", t=0.0)
        rec.record(5, "CANCELLED", t=1.0)
        assert rec.breakdowns() == []
        assert rec.live_requests() == 0
        assert rec.stats()["lifecycle_requests_total"] == 1.0

    def test_unknown_event_raises(self):
        rec = self._rec()
        with pytest.raises(ValueError, match="unknown lifecycle event"):
            rec.record(1, "TELEPORTED")

    def test_event_cap_counts_drops(self):
        rec = self._rec(max_events_per_request=3)
        rec.record(6, "SUBMIT", t=0.0)
        rec.record(6, "ADMITTED", t=0.1)
        rec.record(6, "FIRST_TOKEN", t=0.2)
        for i in range(5):
            rec.record(6, "TOKEN_STREAMED", t=0.3 + i * 0.1, n=1)
        assert rec.stats()["lifecycle_dropped_total"] == 5.0

    def test_jsonl_export(self, tmp_path):
        path = str(tmp_path / "lifecycle.jsonl")
        with self._rec(jsonl_path=path) as rec:
            rec.record(7, "SUBMIT", t=0.0, prompt_len=4)
            rec.record(7, "ADMITTED", t=0.5, slot=2)
            rec.record(7, "RETIRED", t=1.0, tokens=3)
        lines = [json.loads(x) for x in open(path).read().splitlines()]
        assert [x["event"] for x in lines] == \
            ["SUBMIT", "ADMITTED", "RETIRED"]
        assert lines[0]["rid"] == 7 and lines[0]["prompt_len"] == 4
        assert lines[1]["slot"] == 2

    def test_thread_safety_smoke(self):
        rec = self._rec()
        errors = []

        def worker(base):
            try:
                for i in range(200):
                    rid = base * 1000 + i
                    rec.record(rid, "SUBMIT", t=float(i))
                    rec.record(rid, "ADMITTED", t=float(i) + 0.1)
                    rec.record(rid, "RETIRED", t=float(i) + 0.2)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert rec.stats()["lifecycle_requests_total"] == 1600.0


class TestTracerDropsAndFlows:
    def test_ring_eviction_counts_dropped(self):
        t = Tracer(capacity=4, enabled=True)
        for i in range(10):
            t.add_span(f"s{i}", start=float(i), end=float(i) + 0.5)
        assert t.dropped_events == 6
        s = t.stats()
        assert s["trace_events"] == 4.0
        assert s["trace_dropped_events"] == 6.0
        t.clear()
        assert t.dropped_events == 0

    def test_disabled_tracer_drops_nothing(self):
        t = Tracer(capacity=2)
        for i in range(5):
            t.add_instant(f"i{i}")
        assert t.dropped_events == 0 and len(t) == 0

    def test_flow_events_link_lanes(self, tmp_path):
        t = Tracer(enabled=True)
        t.add_flow("request", id=7, phase="s", cat="gateway",
                   tid=7, t=1.0)
        t.add_flow("request", id=7, phase="f", cat="serve", tid=7, t=2.0)
        evs = t.events()
        assert [e["ph"] for e in evs] == ["s", "f"]
        assert all(e["id"] == 7 for e in evs)
        assert evs[1]["bp"] == "e" and "bp" not in evs[0]
        path = str(tmp_path / "flow.json")
        assert t.write(path) == 2
        doc = json.load(open(path))
        flows = [e for e in doc["traceEvents"] if e["ph"] in ("s", "f")]
        assert len(flows) == 2

    def test_flow_rejects_bad_phase(self):
        t = Tracer(enabled=True)
        with pytest.raises(ValueError, match="flow phase"):
            t.add_flow("request", id=1, phase="x")


class TestMetricsServerConcurrentScrape:
    """A scrape that lands mid-write must still render a complete,
    valid Prometheus text page — 8 writer threads hammer the registry
    while 8 scraper threads pull /metrics."""

    _LINE = __import__("re").compile(
        r"^(#.*|[A-Za-z_:][A-Za-z0-9_:]*(\{[^}]*\})? "
        r"[-+0-9.eE]+(inf|nan)?)$")

    def test_mid_write_scrape_is_valid_text(self):
        r = Registry()
        c = r.counter("dtt_stress_total", "stress counter",
                      labelnames=("worker",))
        h = r.histogram("dtt_stress_seconds", "stress histogram",
                        buckets=(0.01, 0.1, 1.0))
        stop = threading.Event()
        errors = []

        def writer(k):
            i = 0
            while not stop.is_set():
                c.labels(worker=str(k)).inc()
                h.observe((i % 100) / 50.0)
                i += 1

        with MetricsServer(port=0, registry=r, host="127.0.0.1") as srv:
            url = f"http://127.0.0.1:{srv.port}/metrics"

            def scraper():
                try:
                    for _ in range(12):
                        body = urllib.request.urlopen(
                            url, timeout=10).read().decode()
                        assert body.endswith("\n")
                        for ln in body.splitlines():
                            assert self._LINE.match(ln), ln
                        assert "dtt_stress_seconds_count" in body
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            writers = [threading.Thread(target=writer, args=(k,),
                                        daemon=True) for k in range(8)]
            scrapers = [threading.Thread(target=scraper)
                        for _ in range(8)]
            for t in writers + scrapers:
                t.start()
            for t in scrapers:
                t.join(timeout=60)
            stop.set()
            for t in writers:
                t.join(timeout=5)
        assert not errors, errors
