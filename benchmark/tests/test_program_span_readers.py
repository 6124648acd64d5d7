"""The readers of the program's own spans and names, on a made-up run:
a device that idles twice in a 10 s window, a ``XLA Modules`` line with
named launches, a tracer that holds known spans on the host's clock, and
a ``bench/window`` anchor 95 s apart on the two clocks."""

import importlib.util
import os
import types

import pytest

from benchmark.harness import program_spans, spec, xplane
from distributed_tensorflow_tpu.obs import trace as obs_trace

SHIFT = 95.0                    # trace clock = host clock + 95 s
WINDOW = (100.0, 110.0)         # on the trace's clock


def reader(name):
    path = os.path.join(spec.BENCH_DIR, "readers", f"{name}.py")
    module_spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module.read


def device_trace():
    op = lambda a, b: xplane.Event("%fusion.1 = bf16[8]{0} fusion(%p)", a, b)
    launch = lambda name, a, b: xplane.Event(name, a, b)
    lines = {
        # Busy 100-102, 102.5-106, 107-110: idle (102, 102.5) and (106, 107).
        xplane.OPS_LINE: [op(99.0, 102.0), op(102.5, 106.0), op(107.0, 110.5)],
        xplane.MODULES_LINE: [
            launch("jit_prefill_slots(11)", 99.5, 100.2),   # began before
            launch("jit_prefill_slots(11)", 100.2, 100.7),
            launch("jit_decode_megastep(7)", 100.7, 102.0),
            launch("jit_prefill_slots(12)", 102.5, 102.9),
            launch("jit_prefill_slots(11)", 107.0, 107.3),
        ],
    }
    return xplane.Trace({0: lines}, [])


@pytest.fixture
def tracer(monkeypatch):
    t = obs_trace.Tracer(enabled=True)
    monkeypatch.setattr(obs_trace, "_default_tracer", t)
    return t


def context(lines_said):
    return {
        "profile": {"trace": device_trace(), "window": WINDOW,
                    "window_s": 10.0},
        "spans": types.SimpleNamespace(by_name={"window": [(5.0, 15.0)]}),
        "say": lambda event, **fields: lines_said.append((event, fields)),
    }


def loop_span(t, ids, name, start, end, cat="serve"):
    """A context-managed span as the ring holds it, on the host's clock."""
    t.add_span(name, cat=cat, start=start - SHIFT, end=end - SHIFT,
               args={"span_id": next(ids)})


def fill_serving(t):
    ids = iter(range(1, 100))
    for start, end in [(100.0, 102.5), (102.5, 106.0), (106.0, 107.2),
                       (107.2, 111.0)]:           # the last outlives the window
        loop_span(t, ids, "iteration", start, end)
    loop_span(t, ids, "idle_wait", 106.1, 106.9)
    loop_span(t, ids, "fetch", 100.5, 102.0)
    loop_span(t, ids, "fetch", 103.0, 105.5)
    loop_span(t, ids, "host_sched", 102.0, 102.4)
    # Ring only (no span_id): a slot's lane, from before the window to
    # after it.
    for start, end in [(99.8, 100.2), (101.0, 101.5), (104.0, 104.3),
                       (109.5, 110.9)]:
        t.add_span("slot_turnover", cat="serve", tid=3,
                   start=start - SHIFT, end=end - SHIFT,
                   args={"wait_iteration_s": 0.1, "wait_prefill_s": 0.2,
                         "wait_launch_s": end - start - 0.3})


def test_serving_readers_on_known_spans(tracer):
    fill_serving(tracer)
    said = []
    ctx = context(said)
    # Turnovers that end in the window: 0.4, 0.5 and 0.3 s.
    assert reader("program_span_median_ms")(
        ctx, span="dtt/serve/slot_turnover") == pytest.approx(400.0)
    # Iterations cover the 10 s; parked 0.8 s, blocked 1.5 + 2.5 s.
    assert reader("program_span_busy_pct")(
        ctx, span="dtt/serve/iteration",
        less=["dtt/serve/idle_wait", "dtt/serve/fetch"]) == pytest.approx(52.0)
    # Idle 0.5 + 1.0 s, of which 0.8 s parked for want of work.
    assert reader("program_idle_pct")(
        ctx, span="dtt/serve/idle_wait") == pytest.approx(7.0)
    # Three prefill launches lie inside the window: 0.5, 0.4 and 0.3 s.
    assert reader("named_module_device_ms")(
        ctx, prefix="jit_prefill_slots(") == pytest.approx(400.0)
    assert reader("named_module_device_ms")(ctx, prefix="jit_verify") is None
    # One line a run, whatever the number of readers.
    (event, line), = said
    assert event == "program_spans"
    assert line["anchor_shift_s"] == pytest.approx(SHIFT)
    assert line["spans"]["serve/iteration"]["count"] == 4
    assert line["spans"]["serve/iteration"]["total_s"] == pytest.approx(10.0)
    turnover = line["spans"]["serve/slot_turnover"]
    assert turnover["count"] == 4 and turnover["ended"] == 3
    assert turnover["median_ms"] == pytest.approx(400.0)    # the metric's
    assert turnover["median_wait_prefill_s"] == 0.2
    assert turnover["median_wait_launch_s"] == pytest.approx(0.1)
    # Idle time goes to the innermost loop span that covers it; requests'
    # lanes (the turnovers) take none of it.
    assert line["idle_s_by_span"] == pytest.approx({
        "serve/idle_wait": 0.8, "serve/host_sched": 0.4,
        "serve/iteration": 0.3})
    # The launches inside the window, by the name the program gave.
    assert line["launches"]["jit_prefill_slots"] == {
        "count": 3, "device_s": pytest.approx(1.2)}
    assert line["launches"]["jit_decode_megastep"]["count"] == 1


def test_training_reader_and_what_no_span_covers(tracer):
    ids = iter(range(1, 100))
    for start in (100.2, 103.2, 106.2):
        loop_span(tracer, ids, "step", start, start + 2.8, cat="train")
        loop_span(tracer, ids, "metrics_fetch", start + 0.3, start + 2.7,
                  cat="train")
    said = []
    ctx = context(said)
    assert reader("program_span_busy_pct")(
        ctx, span="dtt/train/step",
        less=["dtt/train/metrics_fetch"]) == pytest.approx(12.0)
    assert reader("program_span_busy_pct")(
        ctx, span="dtt/serve/iteration") is None
    # (102, 102.5) lies in a fetch; of (106, 107) the first 0.2 s lie
    # between two steps, then 0.3 s in the step, then in its fetch.
    assert said[0][1]["idle_s_by_span"] == pytest.approx({
        "train/metrics_fetch": 1.0, "train/step": 0.3, "unattributed": 0.2})
    (at, length), = said[0][1]["unattributed_at"]
    assert (at, length) == pytest.approx((6.0, 0.2))


def test_nothing_to_read_gives_none(tracer, monkeypatch):
    """An empty ring, and a tracer that has no ``spans`` (the program
    before it recorded any): every reader leaves its metric out."""
    calls = [("program_span_median_ms", {"span": "dtt/serve/slot_turnover"}),
             ("program_span_busy_pct", {"span": "dtt/train/step"}),
             ("program_idle_pct", {"span": "dtt/serve/idle_wait"})]
    for name, args in calls:
        said = []
        assert reader(name)(context(said), **args) is None
        # The line still says which programs were launched.
        (event, line), = said
        assert not line["spans"] and "jit_prefill_slots" in line["launches"]
    monkeypatch.setattr(obs_trace, "_default_tracer", object())
    for name, args in calls:
        assert reader(name)(context([]), **args) is None
    untraced = {"spans": types.SimpleNamespace(by_name={}), "profile": None}
    assert reader("named_module_device_ms")(
        untraced, prefix="jit_prefill_slots(") is None
    fill = obs_trace.Tracer(enabled=True)
    monkeypatch.setattr(obs_trace, "_default_tracer", fill)
    fill_serving(fill)
    assert reader("program_idle_pct")(
        dict(untraced), span="dtt/serve/idle_wait") is None
    assert program_spans.collect(dict(untraced)) is None
