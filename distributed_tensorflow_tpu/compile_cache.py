"""Where JAX's persistent compilation cache lives, and what every compile
cost — one rule, every entry point.

``train.py``, ``serve.py``, ``chip_smoke.py``, ``benchmark/run.py`` and the
scripts all call ``configure()`` before their first compile:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set in
  code, so whoever launches the program places the cache.
- unset: one fixed directory inside the checkout (``.jax_cache/``, ignored
  by git).  The path is part of the cache key, so it never carries a
  temporary name, a pid or a time — two processes started from the same
  checkout share their compiles.

Being the one place every process passes before it compiles, it is also
where the program starts to listen to what JAX says of its compiles
(``jax.monitoring``; here and not in ``obs/trace.py``, which stays free of
``jax``).  Every Python trace, lowering and backend compile that JAX times
becomes a span of the one recorder, ``dtt/compile/trace`` | ``lower`` |
``backend``, with the program's name (``jit_<function>``) as ``program``
and the compiling thread's name as ``thread``; a ``backend`` span also
says ``cache``: ``hit`` (read from the persistent cache, with
``retrieval_s`` and the ``saved_s`` JAX reckons), ``miss`` (compiled, the
cache asked first: JAX's own ``cache_misses`` event counts only the misses
it then writes, which leaves out a program under the cache's minimum
compile time) or ``off`` (compiled, no cache asked).
The category is always recorded (``obs/trace.py``), after warm-up as
before it, so a compile in steady state has a name, three durations, a
cache outcome and, as ``parent``, the loop's span that met it.  JAX
reports a stage when it ends, so a span's start is its end less the
seconds reported; an inner jitted function's trace (kept from
``TRACE_FLOOR_S`` up) lies inside its outer's, and whoever adds these up
takes the union of one thread's intervals, not their sum.  Two counters
for ``/metrics``: ``dtt_compile_seconds_total{program,stage}`` and
``dtt_compiles_total{program,cache}``.
"""

from __future__ import annotations

import os
import re
import threading

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
#: A trace shorter than this is left out.  JAX raises the event for every
#: jitted function met while tracing, ``jax.numpy``'s own included: a
#: thousand for a tiny model's step, all but a handful under a millisecond
#: and each inside its outer's span, where its time is counted.
TRACE_FLOOR_S = 0.01
_MODULE_NAME = re.compile(r"(\w+)\((.*)\)")
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "asked",
    "/jax/compilation_cache/cache_hits": "hit",
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}


class CompileListener:
    """Turns JAX's monitoring events into ``dtt/compile/*`` spans and the
    two counters.  It runs on whichever thread compiles and takes the
    tracer's and the registry's own locks, no other."""

    def __init__(self, tracer, registry, now):
        self._tracer = tracer
        self._now = now         # the tracer's clock
        self._seconds = registry.counter(
            "dtt_compile_seconds_total",
            "Seconds JAX spent on a program by stage: trace (Python to "
            "jaxpr, inner jitted functions' traces included in their "
            "outer's), lower (jaxpr to MLIR), backend (XLA compile, or "
            "the read from the persistent cache)",
            labelnames=("program", "stage"))
        self._compiles = registry.counter(
            "dtt_compiles_total",
            "Backend compiles by program and persistent-cache outcome: "
            "hit (read), miss (compiled), off (compiled, cache not asked)",
            labelnames=("program", "cache"))
        # What the cache said on this thread since its last backend span.
        self._said = threading.local()

    def on_event(self, event: str, **_) -> None:
        word = _CACHE_EVENTS.get(event)
        if word is not None:
            self._said.__dict__[word] = True

    def on_duration(self, event: str, seconds: float, **kwargs) -> None:
        key = _CACHE_SECONDS.get(event)
        if key is not None:
            self._said.__dict__[key] = float(seconds)
            return
        stage = _STAGES.get(event)
        if stage is None or (stage == "trace" and seconds < TRACE_FLOOR_S):
            return
        end = self._now()
        # The trace stage names the function (``step``), the later two
        # the module (``jit(step)``): one name for all three.
        name = str(kwargs.get("fun_name", "unknown"))
        module = _MODULE_NAME.fullmatch(name)
        program = f"{module[1]}_{module[2]}" if module else f"jit_{name}"
        args = {"program": program,
                "thread": threading.current_thread().name}
        if stage == "backend":
            said = self._said.__dict__
            if said.get("hit"):
                args.update(cache="hit", **{
                    k: said[k] for k in _CACHE_SECONDS.values() if k in said})
            else:
                args["cache"] = "miss" if said.get("asked") else "off"
            said.clear()
            self._compiles.labels(program=program, cache=args["cache"]).inc()
        self._seconds.labels(program=program, stage=stage).inc(seconds)
        self._tracer.add_span(stage, cat="compile", start=end - seconds,
                              end=end, args=args)


_listener = None
_listener_lock = threading.Lock()


def listen() -> None:
    """Register the process's one ``CompileListener``, once (``configure``
    does; a process that places no cache, a test, may call it alone)."""
    global _listener
    with _listener_lock:
        if _listener is not None:
            return
        import jax.monitoring

        from distributed_tensorflow_tpu.obs.metrics import default_registry
        from distributed_tensorflow_tpu.obs.trace import default_tracer, now

        _listener = CompileListener(default_tracer(), default_registry(), now)
        jax.monitoring.register_event_listener(_listener.on_event)
        jax.monitoring.register_event_duration_secs_listener(
            _listener.on_duration)


def configure() -> str:
    """Place the compile cache and start listening to the process's
    compiles; returns the directory in use."""
    listen()
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
