"""The benchmark's own host spans: a named interval on the host clock,
also written into the profiler's trace (``TraceAnnotation``) so that an
idle gap on the device can be laid against what the host was doing; and
watches for the times the host was taken from the process, for the
garbage collector's pauses and for XLA's compilations."""

from __future__ import annotations

import contextlib
import gc
import threading
import time
from typing import Dict, List, Tuple

import jax


class Spans:
    def __init__(self):
        self.by_name: Dict[str, List[Tuple[float, float]]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation(f"bench/{name}"):
            start = time.perf_counter()
            try:
                yield
            finally:
                self.by_name.setdefault(name, []).append(
                    (start, time.perf_counter()))

    def durations(self, name: str, since: float = 0.0) -> List[float]:
        return [end - start for start, end in self.by_name.get(name, [])
                if start >= since]

    def clear(self):
        self.by_name.clear()


class HostStalls:
    """How late the host wakes a thread that asks for ``tick`` seconds of
    sleep, while it is open: the longest such delay and the time of all
    over 50 ms.  A host that is taken away from the process (its cores are
    shared on a one-chip machine), or a call that keeps the interpreter's
    lock, shows here whether or not the device had to wait for it."""

    def __init__(self, tick=0.02):
        self.tick, self.longest, self.total = tick, 0.0, 0.0
        self.longest_ended = None       # perf_counter when it woke
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)

    def _watch(self):
        last = time.perf_counter()
        while not self._stop.wait(self.tick):
            now = time.perf_counter()
            late = now - last - self.tick
            if late > self.longest:
                self.longest, self.longest_ended = late, now
            if late > 0.05:
                self.total += late
            last = now

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()


class GcPauses:
    """Time the garbage collector takes from the host while it is open."""

    def __init__(self):
        self.seconds, self.count, self._start = 0.0, 0, None
        self.longest = 0.0

    def _watch(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            pause = time.perf_counter() - self._start
            self.seconds += pause
            self.longest = max(self.longest, pause)
            self.count += 1
            self._start = None

    def __enter__(self):
        gc.callbacks.append(self._watch)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._watch)


class XlaCompiles:
    """Every program XLA compiles, or reads from the compile cache, between
    ``start`` and ``stop``, by JAX's own monitoring event: ``(perf_counter when it
    ended, seconds)``.  It sees what a program's own compile counters do
    not: a jitted helper met at a new shape."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.ended = []

    def _listen(self, event, seconds, **_):
        if event == self.EVENT:
            self.ended.append((time.perf_counter(), seconds))

    def start(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def stop(self):
        jax.monitoring.unregister_event_duration_listener(self._listen)
