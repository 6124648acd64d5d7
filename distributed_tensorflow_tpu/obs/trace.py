"""Span tracing: a bounded in-process flight recorder, Perfetto-loadable.

The program's ONE span source.  Instrumented code (the continuous
scheduler's loop, ``TrainLoop``, the gateway, the router, the checkpoint
manager) emits spans into a ring buffer; :func:`Tracer.chrome_trace`
renders the buffer as Chrome trace-event JSON — open the file in Perfetto
(https://ui.perfetto.dev) or ``chrome://tracing`` — and
:func:`Tracer.spans` hands the raw intervals to whoever wants to lay them
beside a device trace.

**When it records.**  Whenever the tracer was enabled (``--trace_out``)
OR a JAX profiler session is open (``jax.profiler.start_trace``,
``ProfilerHook``, ``obs.profiling.Profile``, a remote capture through
``start_profiler_server``): the profiler's own
``TraceAnnotation.is_enabled()`` is the switch, so no flag has to be
threaded to the code that is profiled.  With neither, a span costs two
clock reads and a small object (about half a microsecond), and nothing is
appended.  **Two categories are the exception and are always recorded:**
``startup`` (the phases between process start and the loop: a workload
made, a step built, an engine and a scheduler constructed, a program's
first launch) and ``compile`` (``compile_cache.py``'s listener: every
trace, lowering and compile-or-cache-read JAX reports, by program).  They
are once-a-process or once-a-compile work, a few dozen appends in a run
and none of them inside a decode launch or a training step, and set-up is
over before anybody could have asked for it to be recorded:
``Tracer.spans(cat="startup")`` reads them.

**One clock.**  Every timestamp is :func:`now` (``time.perf_counter``)
seconds.  Call sites that stamp a span's ends themselves (``add_span``)
read the same function, so a reader never has to assume that two Python
clocks agree.

**Also in the profiler's trace.**  A context-managed span
(``with tracer.span(...)``) additionally enters
``jax.profiler.TraceAnnotation("dtt/<cat>/<name>")`` while a profiler
session is open, so the loop's phases lie on the ``/host:CPU`` lines of
the same xplane file as the device's lines, on the profiler's own clock.
Spans recorded after the fact (``add_span`` with explicit times: a
request's ``queue_wait``, ``prefill``, ``decode``; a compile's stages)
stay ring-only, and carry as ``parent`` the span that was open on the
calling thread when they were recorded.

The ring buffer bounds memory: a long-running server keeps only the most
recent ``capacity`` events — a flight recorder, not an archive.
"""

from __future__ import annotations

import collections
import functools
import itertools
import json
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = ["ALWAYS_RECORDED", "Tracer", "default_tracer", "now", "span_name",
           "spanned"]

#: The tracer's clock, in seconds.  ``benchmark/harness/spans.py`` stamps
#: its spans with the same function.
now = time.perf_counter

_profiler_on = TraceAnnotation.is_enabled

#: Categories that land in the ring whether or not anything records (the
#: module docstring says why these two).
ALWAYS_RECORDED = frozenset({"startup", "compile"})

Span = Tuple[str, float, float, int, Dict[str, Any]]


def span_name(cat: str, name: str) -> str:
    """A span's full name, ``dtt/<cat>/<name>``: what the profiler's trace
    shows for a context-managed span and what :func:`Tracer.spans`
    returns."""
    return f"dtt/{cat or 'default'}/{name}"


class _OpenSpan:
    """A context-managed span while it is open.  ``set(**args)`` adds
    arguments that are known only at its end.  It lands in the ring if
    the tracer records when it opens OR when it closes: a span that was
    already open when a profiler session began (the iteration in progress,
    the park of an idle server) or is still open when it ends is recorded
    whole, so neither end of a profile is a hole.  Only the profiler's own
    trace needs the session at both ends."""

    __slots__ = ("_tracer", "_name", "_cat", "_tid", "_args", "_annotation",
                 "_start", "_span_id", "_parent", "_record")

    def __init__(self, tracer, name, cat, tid, args):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._tid = tid
        self._args = args
        self._annotation = None

    def set(self, **args) -> None:
        self._args = {**self._args, **args} if self._args else args

    def __enter__(self):
        tracer = self._tracer
        try:
            stack = tracer._local.stack   # this thread's open span ids
        except AttributeError:
            stack = tracer._local.stack = []
        self._parent = stack[-1] if stack else None
        self._span_id = span_id = next(tracer._ids)
        stack.append(span_id)
        # The annotation first and the clock second, as the benchmark's
        # own spans do: the two stamps of one instant lie some
        # microseconds apart, in the same order everywhere.
        self._record = tracer._enabled or self._cat in ALWAYS_RECORDED
        if _profiler_on():
            self._record = True
            self._annotation = TraceAnnotation(
                span_name(self._cat, self._name))
            self._annotation.__enter__()
        self._start = now()
        return self

    def __exit__(self, *exc):
        end = now()
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        tracer = self._tracer
        tracer._local.stack.pop()
        if self._record or tracer._enabled or _profiler_on():
            args = dict(self._args) if self._args else {}
            args["span_id"] = self._span_id
            if self._parent is not None:
                args["parent"] = self._parent
            tracer._record_span(self._name, self._start, end, self._cat,
                                self._tid, args)
        return False


class Tracer:
    """Bounded ring buffer of Chrome trace events.

    Events follow the trace-event JSON spec: complete spans (``ph="X"``,
    explicit ``ts``/``dur`` in µs since the tracer's epoch) and instants
    (``ph="i"``).  ``tid`` distinguishes timelines — the serve
    instrumentation uses the request id so Perfetto renders one lane per
    request, ``0`` for the scheduler loop itself.
    """

    def __init__(self, capacity: int = 16384, *, enabled: bool = False):
        # (chrome event, start_s, end_s): the raw ends are kept beside the
        # rendered microseconds so spans() loses nothing to rounding.
        self._events: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._epoch = now()
        self._enabled = enabled
        self._dropped = 0
        self._drop_metric = None
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _append(self, ev: Dict[str, Any], start: float = 0.0,
                end: float = 0.0) -> None:
        """Ring append that counts evictions — a truncated flight
        recording must never be mistaken for a complete one."""
        metric = None
        with self._lock:
            if (self._events.maxlen is not None
                    and len(self._events) == self._events.maxlen):
                self._dropped += 1
                if self._drop_metric is None:
                    # Lazy so this module stays dependency-free at
                    # import time (obs/__init__ requires metrics/trace
                    # to import nothing from the package).
                    from distributed_tensorflow_tpu.obs.metrics import (
                        default_registry)

                    self._drop_metric = default_registry().counter(
                        "dtt_trace_dropped_total",
                        "trace ring-buffer events evicted before export")
                metric = self._drop_metric
            self._events.append((ev, start, end))
        if metric is not None:
            metric.inc()

    @property
    def dropped_events(self) -> int:
        """Events evicted from the ring since construction/clear()."""
        with self._lock:
            return self._dropped

    def stats(self) -> Dict[str, float]:
        with self._lock:
            return {
                "trace_enabled": float(self._enabled),
                "trace_events": float(len(self._events)),
                "trace_dropped_events": float(self._dropped),
            }

    @property
    def enabled(self) -> bool:
        """Whether ``enable()`` switched the ring on (``--trace_out``).
        A profiler session records without it: see ``recording``."""
        return self._enabled

    @enabled.setter
    def enabled(self, value: bool) -> None:
        self._enabled = bool(value)

    @property
    def recording(self) -> bool:
        """Whether a span emitted now lands in the ring: the tracer is
        enabled, or a JAX profiler session is open.  Lock-free; this is
        the hot path's guard."""
        return self._enabled or _profiler_on()

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def _us(self, t: float) -> int:
        return int((t - self._epoch) * 1e6)

    def _record_span(self, name, start, end, cat, tid, args) -> None:
        ev = {
            "name": name,
            "cat": cat or "default",
            "ph": "X",
            "ts": self._us(start),
            "dur": max(0, self._us(end) - self._us(start)),
            "pid": 0,
            "tid": int(tid),
        }
        if args:
            ev["args"] = args
        self._append(ev, start, end)

    def add_span(
        self,
        name: str,
        *,
        start: float,
        end: float,
        cat: str = "",
        tid: int = 0,
        args: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Record a completed span; ``start``/``end`` are ``now()`` times.
        Ring only: it never enters the profiler's trace.  Its ``args``
        carry ``parent``, the ``span_id`` of the innermost span open on
        the calling thread, where there is one: a span recorded after the
        fact says which of the program's spans it fell in."""
        if self.recording or cat in ALWAYS_RECORDED:
            args = dict(args) if args else {}
            stack = getattr(self._local, "stack", None)
            if stack:
                args["parent"] = stack[-1]
            self._record_span(name, start, end, cat, tid, args)

    def add_instant(
        self,
        name: str,
        *,
        cat: str = "",
        tid: int = 0,
        args: Optional[Dict[str, Any]] = None,
    ) -> None:
        if not self.recording:
            return
        ev = {
            "name": name,
            "cat": cat or "default",
            "ph": "i",
            "s": "t",
            "ts": self._us(now()),
            "pid": 0,
            "tid": int(tid),
        }
        if args:
            ev["args"] = dict(args)
        self._append(ev)

    def add_flow(
        self,
        name: str,
        *,
        id: int,
        phase: str,
        cat: str = "",
        tid: int = 0,
        t: Optional[float] = None,
    ) -> None:
        """Record a flow event (``phase``: "s" start, "t" step, "f"
        finish).  Flows with the same ``id`` draw connecting arrows in
        Perfetto — the serve path uses the request id to link the
        gateway span to the scheduler's per-rid lane."""
        if phase not in ("s", "t", "f"):
            raise ValueError(f"flow phase must be s/t/f, got {phase!r}")
        if not self.recording:
            return
        ev = {
            "name": name,
            "cat": cat or "flow",
            "ph": phase,
            "id": int(id),
            "ts": self._us(now() if t is None else t),
            "pid": 0,
            "tid": int(tid),
        }
        if phase == "f":
            ev["bp"] = "e"  # bind to the enclosing slice's end
        self._append(ev)

    def span(
        self,
        name: str,
        cat: str = "",
        tid: int = 0,
        args: Optional[Dict[str, Any]] = None,
    ):
        """``with tracer.span("prefill_chunk", cat="serve") as s: ...``
        times the body.  ``s.set(k=v)`` adds arguments known only at the
        end.  The recorded ``args`` carry ``span_id`` and, for a span
        opened inside another on the same thread, ``parent`` (the
        enclosing span's ``span_id``), so that self time — a span less
        what its children cover — can be computed.  While a profiler
        session is open the body also runs inside
        ``TraceAnnotation("dtt/<cat>/<name>")``.  The span is recorded if
        the tracer records when it opens or when it closes."""
        return _OpenSpan(self, name, cat, tid, args)

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [ev for ev, _, _ in self._events]

    def spans(self, name: Optional[str] = None,
              cat: Optional[str] = None) -> List[Span]:
        """The complete spans in the ring, oldest first, as
        ``(name, start_s, end_s, tid, args)`` with the full name
        ``dtt/<cat>/<name>`` and both ends in ``now()`` seconds.
        ``name`` keeps one full name, ``cat`` one category."""
        with self._lock:
            held = list(self._events)
        out: List[Span] = []
        for ev, start, end in held:
            if ev["ph"] != "X" or (cat is not None and ev["cat"] != cat):
                continue
            full = span_name(ev["cat"], ev["name"])
            if name is None or full == name:
                out.append((full, start, end, ev["tid"],
                            dict(ev.get("args", {}))))
        return out

    def chrome_trace(self) -> Dict[str, Any]:
        """The full trace-event JSON document (``{"traceEvents": [...]}``)."""
        meta = {
            "name": "process_name",
            "ph": "M",
            "pid": 0,
            "tid": 0,
            "args": {"name": "distributed_tensorflow_tpu"},
        }
        return {"traceEvents": [meta] + self.events()}

    def write(self, path: str) -> int:
        """Dump the Chrome trace JSON to ``path``; returns the event count."""
        doc = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(doc, f)
        return len(doc["traceEvents"]) - 1  # minus the metadata event


_default_tracer = Tracer()


def default_tracer() -> Tracer:
    """Process-global tracer; entrypoints enable it under ``--trace_out``,
    and it records under any profiler session without that."""
    return _default_tracer


def spanned(name: str, cat: str):
    """Decorator: each call runs inside ``default_tracer().span(name,
    cat)``.  For a body too long to indent under a ``with`` (a
    constructor that is a set-up phase)."""

    def wrap(fn):
        @functools.wraps(fn)
        def inside(*args, **kwargs):
            with _default_tracer.span(name, cat):
                return fn(*args, **kwargs)

        return inside

    return wrap
