"""The traced run: the profiler round the window, and the trace reduced
to what the per-layer readers and the result line need."""

from __future__ import annotations

import contextlib
import os
import shutil
from typing import Any, Dict, Optional

import jax

from benchmark.harness import xplane


class Holder:
    result: Optional[Dict[str, Any]] = None


def reduce(path: str) -> Dict[str, Any]:
    trace = xplane.load(path)
    marks = trace.annotations("bench/window")
    if not marks:
        raise ValueError("the trace holds no bench/window span")
    window = (marks[0].start, marks[0].end)
    busy = xplane.busy_seconds(trace, window)
    if busy <= 0:
        raise ValueError("no operation ran on the device in the traced window")
    return {"trace": trace, "window": window,
            "window_s": window[1] - window[0], "busy_s": busy}


@contextlib.contextmanager
def traced(on: bool, cell, spans):
    """Runs its body as the window; with ``on`` the profiler records it
    (host threads and device, no Python call stacks) and ``holder.result``
    afterwards holds the reduced trace."""
    holder = Holder()
    if not on:
        yield holder
        return
    out = os.path.join(cell.bench_dir, "_out", "trace", cell.name)
    shutil.rmtree(out, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        with spans.span("window"):
            yield holder
    finally:
        jax.profiler.stop_trace()
    holder.result = reduce(xplane.find_xplane(out))
    shutil.rmtree(out, ignore_errors=True)


def breakdown(profile: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """The heaviest device operations and the idle time by what the host
    was doing, on the first traced chip."""
    trace, window = profile["trace"], profile["window"]
    lines = trace.devices[min(trace.devices)]
    ops = xplane.op_seconds_by_name(lines, window)
    gaps = xplane.attribute_gaps(
        xplane.idle_gaps(lines, window),
        [e for e in trace.host if e.name != "bench/window"])
    rank = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(ops), "idle_gaps": rank(gaps)}
