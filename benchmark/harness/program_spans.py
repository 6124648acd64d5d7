"""The program's own spans, laid on the device trace's clock.

The program records its loop's phases itself (``obs/trace.py``: the
scheduler's iteration, the training loop's step and what they contain),
into a ring that is on whenever a profiler session is open, stamped with
``time.perf_counter``.  The harness's ``xplane.load`` keeps only the
benchmark's own annotations and the traced run deletes the trace
directory, so the readers take the spans from the ring and shift them
onto the trace's clock by the one span recorded on both: ``bench/window``
(``ctx["spans"]`` on ``perf_counter``, ``ctx["profile"]["window"]`` on the
trace's clock).  Its two stamps are taken one after the other (the
annotation is entered, then the clock is read), so they lie some
microseconds apart: that is the error of every shifted time here.

A program that records no spans (a commit before it did) gives ``None``,
and the readers built on this leave their metric out of the line.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

from benchmark.harness import xplane
from benchmark.harness.stats import median

PREFIX = "dtt/"
_KEY = "program_spans"


def _ring() -> List[tuple]:
    from distributed_tensorflow_tpu.obs.trace import default_tracer

    spans = getattr(default_tracer(), "spans", None)
    return spans() if spans is not None else []


def collect(ctx) -> Optional[Dict[str, Any]]:
    """The spans that touch the traced window, shifted onto the trace's
    clock: ``all`` maps a span's name to its events cut to the window,
    ``ended`` to those that end inside it, whole, each with its
    arguments, and ``loop`` lists the context-managed ones (what a thread
    was doing then, as against a request's phases recorded after the
    fact).  Computed once a run, and says its one ``program_spans`` line
    then: a row for each span name (``_summary``), the first chip's idle
    seconds by the innermost loop span that covers them (``unattributed``
    where none does), and the programs launched in the window by name."""
    if _KEY in ctx:
        return ctx[_KEY]
    ctx[_KEY] = None
    profile = ctx.get("profile")
    anchor = ctx["spans"].by_name.get("window") if ctx.get("spans") else None
    if not profile or not anchor:
        return None
    window = profile["window"]
    lines = profile["trace"].devices[min(profile["trace"].devices)]
    ring = _ring()
    if not ring:
        if "say" in ctx:
            ctx["say"]("program_spans", spans={},
                       launches=_launches(lines, window))
        return None
    shift = window[0] - anchor[-1][0]
    by_name: Dict[str, List[xplane.Event]] = {}
    ended: Dict[str, List[tuple]] = {}
    loop: List[xplane.Event] = []
    for name, start, end, _tid, args in ring:
        event = xplane.Event(name, start + shift, end + shift)
        cut = xplane.clip([(event.start, event.end)], window)
        if not cut:
            continue
        if window[0] <= event.end <= window[1]:
            ended.setdefault(name, []).append((event, args))
        event = xplane.Event(name, *cut[0])
        by_name.setdefault(name, []).append(event)
        if "span_id" in args:
            loop.append(event)
    if not by_name:
        return None
    out = {"window": window, "all": by_name, "ended": ended, "loop": loop}
    ctx[_KEY] = out
    if "say" in ctx:
        gaps = xplane.idle_gaps(lines, window)
        idle = xplane.attribute_gaps(gaps, loop, prefix=PREFIX)
        bare = sorted(xplane.subtract(
            gaps, xplane.union((e.start, e.end) for e in loop)),
            key=lambda g: g[0] - g[1])[:5]
        ctx["say"](
            "program_spans", anchor_shift_s=shift,
            spans={name[len(PREFIX):]: _summary(events, ended.get(name, []))
                   for name, events in sorted(by_name.items())},
            idle_s_by_span=dict(sorted(idle.items(), key=lambda kv: -kv[1])),
            # The longest idle pieces that no loop span covers: seconds
            # after the window's start, and how long.
            unattributed_at=[[a - window[0], b - a] for a, b in bare],
            launches=_launches(lines, window))
    return out


def _launches(lines, window) -> Dict[str, Dict[str, float]]:
    """The programs launched inside the window on the first chip, by the
    name the ``XLA Modules`` line gives them (without the launch's id):
    how many launches, and their device seconds."""
    out: Dict[str, Dict[str, float]] = {}
    for event in xplane.module_events(lines):
        if window[0] <= event.start and event.end <= window[1]:
            row = out.setdefault(event.name.split("(")[0],
                                 {"count": 0, "device_s": 0.0})
            row["count"] += 1
            row["device_s"] += event.seconds
    return out


def _summary(cut: Sequence[xplane.Event], ended: Sequence[tuple]):
    """One span name's row of the line: how many touch the window and the
    seconds of it they cover; over those that end inside it, whole, the
    median length and the median of every argument that is a float in
    each of them (a turnover's three waits)."""
    out = {"count": len(cut), "total_s": sum(e.seconds for e in cut),
           "ended": len(ended)}
    if ended:
        out["median_ms"] = 1e3 * median([e.seconds for e, _ in ended])
        rows = [args for _, args in ended]
        for key, value in rows[0].items():
            if all(isinstance(r.get(key), float) for r in rows):
                out[f"median_{key}"] = median([r[key] for r in rows])
    return out


def covered(found: Dict[str, Any],
            names: Sequence[str]) -> List[xplane.Interval]:
    """The merged intervals, inside the window, that spans of these names
    cover."""
    return xplane.union((e.start, e.end) for name in names
                        for e in found["all"].get(name, []))
