"""The one rule for where the persistent compile cache lives
(distributed_tensorflow_tpu/compile_cache.py): the environment variable
places it and then nothing is set in code; unset, every process started from
this checkout uses the same fixed directory inside it.  And what the same
module hears of the process's compiles: every trace, lowering and backend
compile a span of the one recorder, by program, with the cache's outcome."""

import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from distributed_tensorflow_tpu import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_REPORT = (
    "import jax\n"
    "from distributed_tensorflow_tpu import compile_cache\n"
    "print(compile_cache.configure())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _configure_in_a_fresh_process(env_value):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop(compile_cache.ENV_VAR, None)
    if env_value is not None:
        env[compile_cache.ENV_VAR] = env_value
    out = subprocess.run(
        [sys.executable, "-c", _REPORT], env=env, cwd=REPO, check=True,
        capture_output=True, text=True, timeout=300).stdout.split()
    return out  # [configure()'s answer, what JAX will use]


def test_env_var_wins_and_nothing_is_set_in_code(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_env_var_places_the_cache_jax_uses(tmp_path):
    assert _configure_in_a_fresh_process(str(tmp_path)) == [str(tmp_path)] * 2


def test_unset_is_one_fixed_dir_in_the_checkout_from_any_process():
    first = _configure_in_a_fresh_process(None)
    second = _configure_in_a_fresh_process(None)
    assert first == second == [os.path.join(REPO, ".jax_cache")] * 2
    assert compile_cache.DEFAULT_DIR == first[0]


def test_no_entry_point_places_a_cache_of_its_own():
    """No ``setdefault`` of the variable and no cache path under /tmp is
    left in the tree: the helper is the only place that decides."""
    offenders = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out", "tests")]
        for name in files:
            if not name.endswith((".py", ".sh")):
                continue
            path = os.path.join(root, name)
            if path == compile_cache.__file__:
                continue
            with open(path) as f:
                text = f.read()
            if re.search(r"/tmp/\w*jax_cache|setdefault\(\s*[\"']"
                         + compile_cache.ENV_VAR, text):
                offenders.append(os.path.relpath(path, REPO))
    assert not offenders, offenders


# -- what the listener records ------------------------------------------------

_COMPILE_TWICE = """
import json, jax, jax.numpy as jnp
from jax._src import monitoring
from distributed_tensorflow_tpu import compile_cache
from distributed_tensorflow_tpu.obs.trace import default_tracer
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
before = len(monitoring.get_event_duration_listeners())
compile_cache.configure()
compile_cache.configure()
registered = len(monitoring.get_event_duration_listeners()) - before

@jax.jit
def inner(x):
    for _ in range(300):        # slow enough to trace to pass the floor
        x = jnp.sin(x) * 1.5
    return x

@jax.jit
def outer(x):
    return inner(x) + inner(x * 2.0) - 1.0

x = jnp.ones((8,))
outer(x).block_until_ready()
jax.clear_caches()
tracer = default_tracer()
with tracer.span("program_first_launch", cat="startup"):
    outer(x).block_until_ready()
print(json.dumps({"registered": registered, "enabled": tracer.enabled,
                  "spans": tracer.spans()}))
"""


@pytest.fixture(scope="module")
def compiled_twice(tmp_path_factory):
    """A fresh process with a cache directory of its own compiles one
    program, drops JAX's in-memory caches and compiles it again."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env[compile_cache.ENV_VAR] = str(tmp_path_factory.mktemp("cache"))
    out = subprocess.run(
        [sys.executable, "-c", _COMPILE_TWICE], env=env, cwd=REPO, check=True,
        capture_output=True, text=True, timeout=600).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_configure_twice_registers_one_listener(compiled_twice):
    assert compiled_twice["registered"] == 1


def test_backend_spans_say_miss_then_hit_by_program(compiled_twice):
    """Recorded with the tracer off; the second compile is a read from the
    persistent cache, says how long the read took, and names the span of
    the program it fell in."""
    assert compiled_twice["enabled"] is False
    spans = compiled_twice["spans"]
    (launch,) = [s for s in spans if s[0] == "dtt/startup/program_first_launch"]
    first, second = [s for s in spans if s[0] == "dtt/compile/backend"
                     and s[4]["program"] == "jit_outer"]
    assert first[4]["cache"] == "miss" and "parent" not in first[4]
    assert second[4]["cache"] == "hit"
    assert second[4]["retrieval_s"] > 0 and "saved_s" in second[4]
    assert second[4]["parent"] == launch[4]["span_id"]
    assert launch[1] <= second[1] <= second[2] <= launch[2]
    assert first[4]["thread"] == second[4]["thread"] == "MainThread"
    for stage in ("trace", "lower"):
        assert [s[4]["program"] for s in spans
                if s[0] == f"dtt/compile/{stage}"].count("jit_outer") == 2


def test_nested_traces_lie_inside_their_outers(compiled_twice):
    """JAX reports an inner jitted function's trace too: it lies inside
    the outer's span, so the union of the two is the outer's and their sum
    is more."""
    from distributed_tensorflow_tpu.obs.startup import union_seconds

    traces = [s for s in compiled_twice["spans"]
              if s[0] == "dtt/compile/trace"]
    outers = [s for s in traces if s[4]["program"] == "jit_outer"]
    inners = [s for s in traces if s[4]["program"] == "jit_inner"]
    assert len(outers) == 2 and len(inners) >= 2
    for outer in outers:
        mine = [s for s in inners if outer[1] <= s[1] and s[2] <= outer[2]]
        assert mine, "no inner trace inside this outer one"
        both = [(s[1], s[2]) for s in mine + [outer]]
        assert union_seconds(both) == pytest.approx(outer[2] - outer[1])
        assert sum(b - a for a, b in both) > outer[2] - outer[1]
    # Short traces (every jnp call raises one) stay out of the ring.
    assert all(s[2] - s[1] >= compile_cache.TRACE_FLOOR_S * 0.999
               for s in traces)


def _ancestors(tracer, span):
    """Names of the recorded spans on ``span``'s parent chain."""
    by_id = {s[4]["span_id"]: s for s in tracer.spans() if "span_id" in s[4]}
    names = []
    while span is not None and "parent" in span[4]:
        span = by_id.get(span[4]["parent"])
        if span is not None:
            names.append(span[0])
    return names


def test_a_steady_state_compile_names_its_program_under_the_loop(mesh_dp):
    """A scheduler warmed for one prompt length meets another: the
    engine's program cache counts nothing (``compile_total`` stands), but
    the compile is on record by program, under the loop's spans."""
    from distributed_tensorflow_tpu.obs.trace import default_tracer
    from distributed_tensorflow_tpu.serve import ContinuousScheduler
    from distributed_tensorflow_tpu.serve.engine import ServeEngine

    compile_cache.listen()
    tracer = default_tracer()
    engine = ServeEngine("gpt2", mesh=mesh_dp, preset="tiny")
    sched = ContinuousScheduler(engine, num_slots=8, max_total_len=32)
    was = tracer.enabled
    try:
        sched.submit(np.arange(4, dtype=np.int32),
                     max_new_tokens=3).result(timeout=600)
        warm = engine.compile_stats()["compile_total"]
        launches = len(tracer.spans(
            name="dtt/startup/program_first_launch"))
        tracer.enable()                 # so that the loop's spans are kept
        at = len(tracer.spans(name="dtt/compile/backend"))
        sched.submit(np.arange(7, dtype=np.int32),
                     max_new_tokens=3).result(timeout=600)
        late = tracer.spans(name="dtt/compile/backend")[at:]
    finally:
        tracer.enabled = was
        sched.close()
        engine.close()
    assert engine.compile_stats()["compile_total"] == warm
    assert len(tracer.spans(
        name="dtt/startup/program_first_launch")) == launches
    (prefill,) = [s for s in late if s[4]["program"] == "jit_prefill_slots"]
    assert prefill[4]["thread"] == "serve-continuous"
    chain = _ancestors(tracer, prefill)
    assert chain[0] == "dtt/serve/prefill_chunk"
    assert chain[-1] == "dtt/serve/iteration"
    # Set-up: the engine's and the scheduler's phases, and the programs'
    # first launches with their compiles inside, tracer off.
    names = [s[0] for s in tracer.spans(cat="startup")]
    for phase in ("engine_init", "params_placed", "scheduler_init",
                  "cache_init"):
        assert f"dtt/startup/{phase}" in names
    kinds = {s[4]["kind"] for s in tracer.spans(
        name="dtt/startup/program_first_launch")}
    assert {"slot_prefill", "slot_megastep"} <= kinds


def test_a_training_run_records_its_phases_and_its_steps_compile(devices8):
    """``get_workload``, ``build_step`` (with where it reduces gradients),
    the state's init and the first step, each once, with the tracer off;
    the step's trace, lowering and compile fall inside ``first_step``."""
    from distributed_tensorflow_tpu import train_lib
    from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh
    from distributed_tensorflow_tpu.models import get_workload
    from distributed_tensorflow_tpu.models.gpt2 import GPT2Config
    from distributed_tensorflow_tpu.obs.trace import default_tracer
    from distributed_tensorflow_tpu.training import FP32, TrainLoop

    compile_cache.listen()
    tracer = default_tracer()
    assert not tracer.enabled
    mark = len(tracer.spans())
    mesh = build_mesh(MeshConfig(data=2), devices8[:2])
    workload = get_workload("gpt2", mesh=mesh, config=GPT2Config.tiny(),
                            batch_size=8, seq_len=16, grad_accum_steps=2)
    init, _, _, step, _ = train_lib.build_step(
        workload, mesh, precision=FP32, grad_accum_steps=2, total_steps=10)
    batch = {"tokens": np.zeros((8, 16), np.int32)}
    loop = TrainLoop(step, init(), iter(lambda: batch, None), metrics_every=1)
    loop.run(2)
    spans = tracer.spans()[mark:]
    by_name = {}
    for span in spans:
        by_name.setdefault(span[0], []).append(span)
    for phase in ("workload", "build_step", "abstract_state", "shardings",
                  "make_step", "state_init", "first_step"):
        assert len(by_name[f"dtt/startup/{phase}"]) == 1, phase
    assert not [n for n in by_name if n.startswith("dtt/train/")]
    (built,) = by_name["dtt/startup/build_step"]
    assert by_name["dtt/startup/workload"][0][4]["model"] == "gpt2"
    assert {k: built[4][k] for k in ("grad_reduce", "data", "accum")} == {
        "grad_reduce": "after_scan", "data": 2, "accum": 2}
    for child in ("abstract_state", "shardings", "make_step"):
        assert by_name[f"dtt/startup/{child}"][0][4]["parent"] == (
            built[4]["span_id"])
    (first,) = by_name["dtt/startup/first_step"]
    for stage in ("trace", "lower", "backend"):
        (mine,) = [s for s in by_name[f"dtt/compile/{stage}"]
                   if s[4]["program"] == "jit_step"]
        assert first[1] <= mine[1] and mine[2] <= first[2], stage
        assert "parent" in mine[4]      # the loop's dispatch span
    (state_init,) = by_name["dtt/startup/state_init"]
    (init_backend,) = [s for s in by_name["dtt/compile/backend"]
                       if s[4]["program"] == "jit_init_fn"]
    assert init_backend[4]["parent"] == state_init[4]["span_id"]
    # More steps, more runs: still one first step.
    loop.run(2)
    assert len(tracer.spans(name="dtt/startup/first_step")) == len(
        [s for s in tracer.spans()[:mark]
         if s[0] == "dtt/startup/first_step"]) + 1
