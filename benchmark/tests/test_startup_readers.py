"""The three readers of set-up (``startup_span_s``, ``startup_compile_s``,
``startup_cache_misses``) on hand-made spans: before and after the window
opens, with and without a span of the program open round a compile, nested
traces, two compiling threads; ``None`` on an empty ring; and the eight
entries that list them, by the manifest's own rules."""

import json
import os
import types

import pytest

from benchmark.harness import spec
from benchmark.tests.test_program_span_readers import reader
from distributed_tensorflow_tpu.obs import trace as obs_trace

OPENED = 100.0      # the window opens here, on the host's clock


@pytest.fixture
def tracer(monkeypatch):
    t = obs_trace.Tracer()      # off: the two categories are recorded anyway
    monkeypatch.setattr(obs_trace, "_default_tracer", t)
    return t


def context(said):
    return {"spans": types.SimpleNamespace(
                by_name={"window": [(OPENED, OPENED + 2.0)]}),
            "say": lambda event, **fields: said.append((event, fields))}


def phase(t, name, start, end, span_id, parent=None, **args):
    args = dict(args, span_id=span_id)
    if parent is not None:
        args["parent"] = parent
    t._record_span(name, start, end, "startup", 0, args)


def compiled(t, stage, program, start, end, *, parent=7, thread="MainThread",
             **args):
    args = dict(args, program=program, thread=thread)
    if parent is not None:
        args["parent"] = parent
    t._record_span(stage, start, end, "compile", 0, args)


def fill_training(t):
    phase(t, "workload", 10.0, 12.0, 1, model="gpt2")
    phase(t, "build_step", 12.0, 20.0, 2, grad_reduce="none")
    phase(t, "abstract_state", 13.0, 18.0, 3, parent=2)
    # init_fn traced inside build_step's eval_shape; an inner function's
    # trace lies inside it.
    compiled(t, "trace", "jit_init_fn", 13.0, 18.0, parent=3)
    compiled(t, "trace", "jit__normal", 14.0, 15.0, parent=3)
    phase(t, "state_init", 30.0, 33.0, 4)
    compiled(t, "lower", "jit_init_fn", 30.0, 31.0, parent=4)
    compiled(t, "backend", "jit_init_fn", 31.0, 33.0, parent=4, cache="hit",
             retrieval_s=1.9, saved_s=20.0)
    # The step, under the loop's dispatch span (not in the ring: tracer off).
    compiled(t, "trace", "jit_step", 40.0, 46.0, parent=9)
    compiled(t, "lower", "jit_step", 46.0, 50.0, parent=9)
    compiled(t, "backend", "jit_step", 50.0, 60.0, parent=9, cache="miss")
    phase(t, "first_step", 40.0, 62.0, 5)
    # The harness's own helpers: no span of the program open round them.
    compiled(t, "trace", "jit_make_params", 34.0, 35.0, parent=None)
    compiled(t, "backend", "jit_make_params", 35.0, 38.0, parent=None,
             cache="miss")
    # After the window opened: the reference's compiles, a recompile.
    compiled(t, "backend", "jit_forward", 110.0, 130.0, parent=None,
             cache="miss")
    compiled(t, "backend", "jit_step", 100.5, 101.0, parent=9, cache="miss")
    phase(t, "program_first_launch", 99.0, 100.5, 6, kind="late")


def test_training_readers_on_known_spans(tracer):
    fill_training(tracer)
    said = []
    ctx = context(said)
    assert reader("startup_span_s")(ctx, spans=[
        "dtt/startup/workload", "dtt/startup/build_step"]) == pytest.approx(10.0)
    assert reader("startup_span_s")(
        ctx, spans=["dtt/startup/engine_init"]) is None
    # Nested traces count once: 5 (init_fn) + 6 (step); lowerings 1 + 4.
    assert reader("startup_compile_s")(
        ctx, stages=["trace"]) == pytest.approx(11.0)
    assert reader("startup_compile_s")(
        ctx, stages=["trace", "lower"]) == pytest.approx(16.0)
    assert reader("startup_compile_s")(
        ctx, stages=["backend"]) == pytest.approx(12.0)
    assert reader("startup_cache_misses")(ctx) == 1.0
    # One line a run, whatever the number of readers.
    (event, line), = said
    assert event == "startup"
    assert line["phases"]["build_step/abstract_state"] == pytest.approx(5.0)
    assert line["phases"]["first_step"] == pytest.approx(22.0)
    assert "program_first_launch[late]" not in line["phases"]
    assert line["programs"]["jit_step"] == {
        "trace_s": pytest.approx(6.0), "lower_s": pytest.approx(4.0),
        "backend_s": pytest.approx(10.0), "cache": {"miss": 1}}
    assert line["programs"]["jit_init_fn"]["cache"] == {"hit": 1}
    assert "jit_make_params" not in line["programs"]
    assert "jit_forward" not in line["harness_helpers"]
    assert line["harness_helpers"]["jit_make_params"]["backend_s"] == (
        pytest.approx(3.0))
    # workload, build_step, state_init, first_step: 2 + 8 + 3 + 22.
    assert line["covered_s"] == pytest.approx(35.0)
    assert line["first_span_to_window_s"] == pytest.approx(90.0)
    json.dumps(line)


def test_two_compiling_threads_are_each_their_own_union(tracer):
    """The engine's init compiles on the main thread while nothing else
    does; the loop thread's compiles overlap it only by accident of the
    clock, and are not merged with it."""
    phase(tracer, "engine_init", 10.0, 30.0, 1)
    phase(tracer, "scheduler_init", 30.0, 36.0, 2)
    compiled(tracer, "backend", "jit_init_fn", 12.0, 20.0, parent=1,
             cache="hit")
    compiled(tracer, "backend", "jit_prefill_slots", 18.0, 22.0, parent=5,
             thread="serve-continuous", cache="hit")
    ctx = context([])
    assert reader("startup_span_s")(ctx, spans=[
        "dtt/startup/engine_init",
        "dtt/startup/scheduler_init"]) == pytest.approx(26.0)
    assert reader("startup_compile_s")(
        ctx, stages=["backend"]) == pytest.approx(12.0)
    assert reader("startup_cache_misses")(ctx) == 0.0
    assert reader("startup_compile_s")(ctx, stages=["trace"]) is None


def test_nothing_recorded_gives_none(tracer):
    """The parent commit's program records neither category, and an
    untraced run has no window: every reader leaves its metric out and no
    line is said."""
    calls = [("startup_span_s", {"spans": ["dtt/startup/build_step"]}),
             ("startup_compile_s", {"stages": ["backend"]}),
             ("startup_cache_misses", {})]
    said = []
    for name, args in calls:
        assert reader(name)(context(said), **args) is None
    # Spans of the loop alone (what the parent does record) change nothing.
    tracer.enable()
    tracer.add_span("iteration", cat="serve", start=1.0, end=2.0)
    for name, args in calls:
        assert reader(name)(context(said), **args) is None
    fill_training(tracer)
    untraced = {"spans": types.SimpleNamespace(by_name={}), "say": said.append}
    for name, args in calls:
        assert reader(name)(dict(untraced), **args) is None
    assert not said


def test_the_eight_entries_move_setup_s_and_list_their_cells():
    with open(os.path.join(spec.REPO_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    kind = {w["name"]: w["name"].split(".")[0] for w in bench["workloads"]}
    mine = [m for m in bench["per_layer"] if m["moves"] == "setup_s"]
    assert [m["name"] for m in mine] == [
        "step_build_s.train", "step_trace_lower_s.train",
        "step_compile_or_load_s.train", "startup_cache_misses.train",
        "engine_init_s.serve", "programs_trace_lower_s.serve",
        "programs_compile_or_load_s.serve", "startup_cache_misses.serve"]
    assert bench["per_layer"][-8:] == mine      # appended, nothing moved
    for entry in mine:
        side = entry["name"].rsplit(".", 1)[1]
        assert entry["better"] == "lower"
        assert sorted(entry["workloads"]) == sorted(
            name for name, k in kind.items() if k == side), entry["name"]
        for workload in entry["workloads"]:
            cell = spec.load_cell(workload)
            metric = next(m for m in cell.per_layer
                          if m["name"] == entry["name"])
            assert metric["reader"].startswith("startup_")
            assert callable(cell.reader(metric))
