"""What a process says of its own set-up, read from the one recorder.

``obs/trace.py`` always records two categories: ``startup`` (the phases
the program itself goes through between process start and its loop:
``dtt/startup/workload``, ``build_step``, ``state_init``, ``first_step``,
``engine_init``, ``scheduler_init``, ``program_first_launch`` and their
children) and ``compile`` (``compile_cache.py``'s listener: each program's
trace, lowering and compile or cache read).  This module holds no span; it
reads them and reports:

- :func:`summary` (:func:`summarize` over the ring): every phase's seconds
  (a child under its parent's name, ``build_step/abstract_state``) and what
  it said of itself (``params_placed/params_cast``: ``leaves_cast``), every
  program's three stages with how often the persistent cache said hit,
  miss or off, and the seconds all of it covers (the union: nested spans
  are not counted twice);
- :func:`report`: logs that summary as one ``startup`` line and sets the
  gauge ``dtt_startup_seconds{phase}``.  ``serve.py`` calls it when it
  declares itself ready, ``train_lib.run`` when the first loss has landed
  (:class:`StartupReportHook`).

A compile in steady state shows in the same two counters
(``dtt_compiles_total``, ``dtt_compile_seconds_total``) and as
``dtt/compile/*`` spans under the loop's span that met it.
"""

from __future__ import annotations

import json
import logging
from typing import Any, Dict, Iterable, List, Optional, Tuple

from distributed_tensorflow_tpu.obs.metrics import Registry, default_registry
from distributed_tensorflow_tpu.obs.trace import Span, Tracer, default_tracer
from distributed_tensorflow_tpu.training.loop import Hook

logger = logging.getLogger(__name__)

_PREFIX = "dtt/startup/"
# Arguments that place a span among the others; the rest are what the
# phase says of itself (``grad_reduce``, ``restored``, ``leaves_cast``).
_STRUCTURAL = ("span_id", "parent", "kind")


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Seconds covered by at least one of the intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def summarize(phases: List[Span], compiles: List[Span]) -> Dict[str, Any]:
    """``startup`` and ``compile`` spans (as ``Tracer.spans`` gives them)
    reduced to ``phases`` (seconds by name, a child under its parent's
    name), ``phase_args`` (what a phase said of itself, under the same
    name), ``programs`` (seconds by stage and the cache's outcomes) and
    ``covered_s`` (the union of them all)."""
    by_id = {args["span_id"]: (name, args) for name, _, _, _, args in phases
             if "span_id" in args}

    def path(name: str, args: Dict[str, Any]) -> str:
        short = name[len(_PREFIX):]
        if "kind" in args:
            short = f"{short}[{args['kind']}]"
        parent = by_id.get(args.get("parent"))
        return f"{path(*parent)}/{short}" if parent else short

    seconds: Dict[str, float] = {}
    said: Dict[str, Dict[str, Any]] = {}
    for name, start, end, _tid, args in phases:
        key = path(name, args)
        seconds[key] = seconds.get(key, 0.0) + (end - start)
        own = {k: v for k, v in args.items() if k not in _STRUCTURAL}
        if own:
            said.setdefault(key, {}).update(own)
    programs: Dict[str, Dict[str, Any]] = {}
    for name, start, end, _tid, args in compiles:
        row = programs.setdefault(args.get("program", "unknown"), {})
        stage = name.rsplit("/", 1)[-1] + "_s"
        row[stage] = row.get(stage, 0.0) + (end - start)
        if "cache" in args:
            cache = row.setdefault("cache", {})
            cache[args["cache"]] = cache.get(args["cache"], 0) + 1
    return {
        "phases": seconds,
        "phase_args": said,
        "programs": programs,
        "covered_s": union_seconds(
            (start, end) for _, start, end, _, _ in phases + compiles),
    }


def summary(tracer: Optional[Tracer] = None) -> Dict[str, Any]:
    """The set-up this process has recorded so far."""
    tracer = tracer or default_tracer()
    return summarize(tracer.spans(cat="startup"), tracer.spans(cat="compile"))


def report(tracer: Optional[Tracer] = None,
           registry: Optional[Registry] = None) -> Dict[str, Any]:
    """Log the ``startup`` line and set ``dtt_startup_seconds{phase}``."""
    said = summary(tracer)
    gauge = (registry or default_registry()).gauge(
        "dtt_startup_seconds",
        "Seconds of each set-up phase the process recorded "
        "(dtt/startup/* spans; a child under its parent's name)",
        labelnames=("phase",))
    for phase, seconds in said["phases"].items():
        gauge.labels(phase=phase).set(seconds)
    logger.info("startup %s", json.dumps(said, sort_keys=True))
    return said


class StartupReportHook(Hook):
    """Reports once, when the first loss has landed: the first step's
    trace, lowering and compile are then behind the process."""

    def __init__(self):
        self.said: Optional[Dict[str, Any]] = None

    def on_metrics(self, loop, metrics_step, metrics):
        if self.said is None:
            self.said = report()


__all__ = ["StartupReportHook", "report", "summarize", "summary",
           "union_seconds"]
