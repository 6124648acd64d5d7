"""The benchmark's own host spans: a named interval on the host clock,
also written into the profiler's trace (``TraceAnnotation``) so that an
idle gap on the device can be laid against what the host was doing."""

from __future__ import annotations

import contextlib
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
