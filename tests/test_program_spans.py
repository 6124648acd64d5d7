"""The program's own spans and program names (``obs/trace.py`` is the one
recorder): what the continuous scheduler's loop and ``TrainLoop`` record,
that nothing is recorded while nothing listens, the slot turnover with its
three waits, and that every program the serve engine jits has a name."""

import ast
import inspect
import time

import numpy as np
import pytest

from distributed_tensorflow_tpu import cluster
from distributed_tensorflow_tpu.obs import default_registry, default_tracer
from distributed_tensorflow_tpu.serve import ContinuousScheduler, ServeEngine
from distributed_tensorflow_tpu.serve import engine as engine_lib
from distributed_tensorflow_tpu.training import FP32, TrainLoop, make_train_step
from tests.test_training import linear_batch, make_linear_state, quadratic_loss

SERVE_LOOP = {"iteration", "host_sched", "dispatch", "fetch", "prefill_chunk",
              "retire"}
TRAIN_LOOP = {"run", "step", "next_batch", "dispatch", "metrics_fetch",
              "hooks"}


@pytest.fixture(scope="module")
def engine():
    import jax

    # One device: two slots are then two slots, and the third request
    # has to wait for a retirement.
    mesh = cluster.build_mesh(cluster.MeshConfig(), jax.devices()[:1])
    eng = ServeEngine("gpt2", mesh=mesh, preset="tiny")
    yield eng
    eng.close()


@pytest.fixture
def tracer():
    t = default_tracer()
    was = t.enabled
    t.clear()
    yield t
    t.enabled = was
    t.clear()


def serve(sched, count, rng, tokens=5):
    futures = [sched.submit(rng.integers(0, 100, size=(8,), dtype=np.int32),
                            max_new_tokens=tokens) for _ in range(count)]
    return [f.result(timeout=300) for f in futures]


def by_name(tracer, cat):
    out = {}
    for name, start, end, tid, args in tracer.spans(cat=cat):
        out.setdefault(name.rsplit("/", 1)[1], []).append(
            (start, end, tid, args))
    return out


@pytest.mark.parametrize("kwargs", [
    {"megastep": 2, "async_decode": True, "cache_mode": "paged",
     "block_size": 8},
    {},
], ids=["paged-megastep-async", "dense-sync"])
def test_scheduler_loop_spans_nest_and_turnovers_add_up(engine, tracer,
                                                        kwargs):
    rng = np.random.default_rng(0)
    with ContinuousScheduler(engine, num_slots=2, max_total_len=64,
                             **kwargs) as sched:
        serve(sched, 2, rng)                   # compiles; nothing listens
        # ... to the loop: set-up's two categories are always recorded
        # (the scheduler made, each program's first launch).
        assert {e["cat"] for e in tracer.events()} <= {"startup", "compile"}
        assert tracer.spans(name="dtt/startup/scheduler_init")
        turnovers = default_registry().histogram(
            "dtt_serve_slot_turnover_seconds").count
        tracer.enable()
        serve(sched, 6, rng)                   # 2 slots: 4 are re-used
        deadline = time.monotonic() + 10.0     # let the loop park itself
        while (not by_name(tracer, "serve").get("idle_wait")
               and time.monotonic() < deadline):
            with sched._cond:
                sched._cond.notify_all()       # a spurious wake ends a park
            time.sleep(0.01)
        tracer.disable()
    spans = by_name(tracer, "serve")
    assert SERVE_LOOP | {"idle_wait", "slot_turnover", "queue_wait",
                         "prefill", "decode"} <= set(spans)
    # The loop's own spans all lie on lane 0, inside an iteration that
    # they name as their ancestor: the iteration that was parked when the
    # tracer was switched on, and the one parked when it was switched off,
    # are recorded too, whole, when they end.
    parents = {args["span_id"]: args.get("parent")
               for rows in spans.values() for _, _, _, args in rows
               if "span_id" in args}
    iterations = {args["span_id"]: (start, end)
                  for start, end, _, args in spans["iteration"]}
    for name in (SERVE_LOOP | {"idle_wait"}) - {"iteration"}:
        for start, end, tid, args in spans[name]:
            assert tid == 0
            top = args["span_id"]
            while parents.get(top) is not None:
                top = parents[top]
            lo, hi = iterations[top]
            assert lo <= start <= end <= hi, name
    assert all("parent" not in args for _, _, _, args in spans["iteration"])
    host = spans["host_sched"]
    assert sum(args["admitted"] for _, _, _, args in host) == 6
    # One turnover per re-used slot, on the slot's lane, each the sum of
    # its three waits; the last two retirements found the queue empty.
    turns = spans["slot_turnover"]
    assert len(turns) == 4 and {tid for _, _, tid, _ in turns} == {0, 1}
    for start, end, _, args in turns:
        waits = [args[k] for k in ("wait_iteration_s", "wait_prefill_s",
                                   "wait_launch_s")]
        assert all(w >= 0 for w in waits)
        assert sum(waits) == pytest.approx(end - start, abs=1e-9)
        assert args["queued_at_retire"] >= 1
        assert args["rid_in"] > args["rid_out"]
    assert default_registry().histogram(
        "dtt_serve_slot_turnover_seconds").count == turnovers + 4


def test_no_turnover_when_nothing_waited_at_retirement(engine, tracer):
    rng = np.random.default_rng(1)
    with ContinuousScheduler(engine, num_slots=2, max_total_len=64) as sched:
        tracer.enable()
        for _ in range(3):                     # one at a time: queue empty
            serve(sched, 1, rng)
        tracer.disable()
    spans = by_name(tracer, "serve")
    assert len(spans["retire"]) == 3 and "slot_turnover" not in spans


def test_train_loop_spans_nest_inside_step(tracer):
    def three_steps():
        loop = TrainLoop(make_train_step(quadratic_loss, precision=FP32),
                         make_linear_state(), iter(linear_batch, None),
                         metrics_every=1)
        loop.run(3)

    three_steps()
    # Nothing listens to the loop; its first step is set-up's.  (By
    # category: a process in which an earlier test registered the compile
    # listener records the step's ``dtt/compile/*`` spans too.)
    assert not tracer.spans(cat="train")
    assert [s[0] for s in tracer.spans(cat="startup")] == [
        "dtt/startup/first_step"]
    tracer.enable()
    three_steps()
    tracer.disable()
    spans = by_name(tracer, "train")
    assert set(spans) == TRAIN_LOOP
    (run_start, run_end, _, run_args), = spans["run"]
    steps = {args["span_id"]: (start, end)
             for start, end, _, args in spans["step"]}
    assert len(steps) == 3 and "parent" not in run_args
    for start, end, _, args in spans["step"]:
        assert args["parent"] == run_args["span_id"]
        assert run_start <= start <= end <= run_end
    for name in ("next_batch", "dispatch"):
        assert len(spans[name]) == 3
    inside = [row for name in TRAIN_LOOP - {"run", "step"}
              for row in spans[name] if row[3]["parent"] in steps]
    # The last fetch and its delivery are flush_metrics', after the last
    # step: children of the run itself.
    assert len(inside) == sum(len(v) for k, v in spans.items()
                              if k not in ("run", "step")) - 2
    for start, end, _, args in inside:
        lo, hi = steps[args["parent"]]
        assert lo <= start <= end <= hi


def test_every_jit_in_the_serve_engine_has_a_name(engine):
    """Statically: every ``jax.jit(...)`` of the module jits ``_named(...)``
    (or a plain ``def``).  And the programs the runs above compiled say
    so themselves."""
    tree = ast.parse(inspect.getsource(engine_lib))
    jits = [n for n in ast.walk(tree) if isinstance(n, ast.Call)
            and ast.unparse(n.func) == "jax.jit"]
    assert len(jits) >= 15
    for call in jits:
        target = call.args[0]
        named = (isinstance(target, ast.Call)
                 and ast.unparse(target.func) == "_named"
                 and isinstance(target.args[0], (ast.Constant, ast.Subscript)))
        assert named or isinstance(target, ast.Name), ast.unparse(call)
    compiled = (list(engine._generate_fns.values())
                + list(engine._cache_init_fns.values())
                + list(engine._block_fns.values()) + [engine._predict_fn])
    names = {fn.__name__ for fn in compiled}
    assert {"prefill_slots", "decode_megastep"} <= names
    assert not any(n.startswith(("_", "<")) for n in names), names


def test_a_named_partial_lowers_under_its_name():
    import jax

    def apply(k, x):
        return x * k

    text = jax.jit(engine_lib._named("decode_megastep", apply, 2)).lower(
        1.0).as_text()
    assert "@jit_decode_megastep" in text and "_unknown" not in text
