"""From a profiler trace to numbers: the one reduction every PR is read by.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  What a TPU trace looks like (checked by hand on a v5e trace, see
PERF.md): a plane ``/device:TPU:<n>`` per chip with the lines ``XLA
Modules`` (one event per launched program, named ``jit_<fn>(<fingerprint>)``),
``XLA Ops`` (one event per executed HLO instruction, named by the
instruction's text ``%name = type opcode(...)``; a ``while`` or
``conditional`` encloses the events of its body) and ``Async XLA Ops``
(copies and collectives in flight); and a plane ``/host:CPU`` whose lines
are host threads, where ``TraceAnnotation`` spans appear under their names.
Times are nanoseconds on one clock for all planes.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import gzip
import heapq
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all",
                      "collective-broadcast")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_TEXT = re.compile(r"^%(?P<name>[^\s=]+) = (?P<type>.*?) (?P<opcode>[a-z][\w\-]*)\(")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float      # seconds
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    devices: Dict[int, Dict[str, List[Event]]]   # chip -> line -> events
    host: List[Event]                            # every host-thread event

    def annotations(self, prefix: str) -> List[Event]:
        return sorted((e for e in self.host if e.name.startswith(prefix)),
                      key=lambda e: e.start)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, host_prefix: str = "bench/") -> Trace:
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        match = _DEVICE_PLANE.match(plane.name)
        if match:
            lines = devices.setdefault(int(match.group(1)), {})
            for line in plane.lines:
                lines.setdefault(line.name, []).extend(
                    Event(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(
                    Event(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name.startswith(host_prefix))
    return Trace(devices, host)


# -- interval arithmetic -------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the merged intervals ``a`` that no interval of the
    merged intervals ``b`` covers."""
    out: List[Interval] = []
    j = 0
    for start, end in a:
        cursor = start
        while j < len(b) and b[j][1] <= cursor:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append((cursor, b[k][0]))
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


# -- device operations -----------------------------------------------------------

@functools.lru_cache(maxsize=None)
def parse_op(name: str) -> Dict[str, str]:
    """``%fusion.3 = bf16[8,128]{...} fusion(...)`` -> name, type, opcode.
    (Memoised: a window executes a few thousand distinct instructions
    hundreds of thousands of times, and every reduction asks again.)"""
    match = _OP_TEXT.match(name)
    if not match:
        return {"name": name.split(" ")[0].lstrip("%"), "type": "",
                "opcode": ""}
    return match.groupdict()


CONTAINER_OPCODES = ("while", "conditional", "call")


def leaf_events(events: Sequence[Event]) -> List[Event]:
    """The instructions that ran, without the ``while``, ``conditional``
    and ``call`` instructions whose events enclose those of their bodies.
    (Told by opcode and not by nesting: a short copy-start can sit inside
    a long kernel's interval without being its child.)"""
    return [e for e in events
            if parse_op(e.name)["opcode"] not in CONTAINER_OPCODES]


def is_collective(event: Event) -> bool:
    opcode = parse_op(event.name)["opcode"]
    return any(opcode == c or opcode.startswith(c + "-")
               for c in COLLECTIVE_OPCODES)


def busy_intervals(lines: Dict[str, List[Event]]) -> List[Interval]:
    """When an operation ran on this chip."""
    return union((e.start, e.end) for e in leaf_events(lines.get(OPS_LINE, [])))


def busy_seconds(trace: Trace, window: Interval) -> float:
    """Busy seconds inside ``window``, averaged over the chips traced."""
    per_chip = [total(clip(busy_intervals(lines), window))
                for lines in trace.devices.values()]
    if not per_chip:
        raise ValueError("the trace holds no device plane")
    return sum(per_chip) / len(per_chip)


def idle_gaps(lines: Dict[str, List[Event]], window: Interval) -> List[Interval]:
    return subtract([window], clip(busy_intervals(lines), window))


def attribute_gaps(gaps: Sequence[Interval], host: Sequence[Event],
                   prefix: str = "bench/") -> Dict[str, float]:
    """Idle seconds by what the host was doing: each gap goes, piece by
    piece, to the innermost (shortest) span that covers the piece.  One
    sweep over the edges of gaps and spans together: a saturated server's
    trace has a gap between every two instructions and thousands of
    spans, and gap by span took minutes."""
    spans = [e for e in host if e.name.startswith(prefix)]
    edges = [(s.end, 0, i) for i, s in enumerate(spans)]
    edges += [(s.start, 1, i) for i, s in enumerate(spans)]
    edges += [(b, 2, -1) for a, b in gaps if b > a]
    edges += [(a, 3, -1) for a, b in gaps if b > a]
    edges.sort()
    out: Dict[str, float] = {}
    open_spans: List[Tuple[float, int]] = []    # heap: (length, span)
    closed = set()
    in_gap, before = False, 0.0
    for at, kind, i in edges:
        if in_gap and at > before:
            while open_spans and open_spans[0][1] in closed:
                heapq.heappop(open_spans)
            key = (spans[open_spans[0][1]].name[len(prefix):]
                   if open_spans else "unattributed")
            out[key] = out.get(key, 0.0) + at - before
        before = at
        if kind == 1:
            heapq.heappush(open_spans, (spans[i].seconds, i))
        elif kind == 0:
            closed.add(i)
        else:
            in_gap = kind == 3
    return out


def module_events(lines: Dict[str, List[Event]], prefix: str = "") -> List[Event]:
    return [e for e in lines.get(MODULES_LINE, []) if e.name.startswith(prefix)]


def op_seconds_by_name(lines: Dict[str, List[Event]],
                       window: Interval) -> Dict[str, float]:
    """Device seconds of each executed instruction, by a short label
    ``name opcode result-type``."""
    out: Dict[str, float] = {}
    for event in leaf_events(lines.get(OPS_LINE, [])):
        inside = clip([(event.start, event.end)], window)
        if not inside:
            continue
        op = parse_op(event.name)
        label = f"{op['name']} {op['opcode']} {op['type']}"[:120]
        out[label] = out.get(label, 0.0) + total(inside)
    return out


def exposed_collective_seconds(lines: Dict[str, List[Event]],
                               window: Interval) -> Dict[str, float]:
    """Seconds a chip spent in collectives, and the part of them during
    which no other operation ran on it."""
    ops = leaf_events(lines.get(OPS_LINE, []))
    collective = union(
        (e.start, e.end) for e in list(ops) + lines.get(ASYNC_LINE, [])
        if is_collective(e))
    compute = union((e.start, e.end) for e in ops if not is_collective(e))
    collective = clip(collective, window)
    return {"collective_s": total(collective),
            "exposed_s": total(subtract(collective, clip(compute, window)))}


# -- Pallas kernels ----------------------------------------------------------------

_KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
_SHAPE = re.compile(r"(bf16|f32|f16)\[(\d+),(\d+),(\d+)\]")


def kernel_calls(lines: Dict[str, List[Event]], window: Interval):
    """Every Pallas (Mosaic) kernel call in the window: its event, and the
    operand shapes its instruction text carries."""
    calls = []
    for event in leaf_events(lines.get(OPS_LINE, [])):
        if _KERNEL_TARGET not in event.name:
            continue
        if not clip([(event.start, event.end)], window):
            continue
        calls.append((event, parse_op(event.name)))
    return calls


def flash_kind(result_type: str):
    """Which of the three flash attention kernels an instruction is, from
    its result type (no kernel carries a name of its own yet): the forward
    returns (out bf16, log-sum-exp f32), the dK/dV kernel two bf16 arrays,
    the dQ kernel one.  Returns (kind, rows, seq, head_dim) or None."""
    shapes = _SHAPE.findall(result_type)
    if not shapes:
        return None
    dtype, rows, seq, dim = shapes[0]
    if len(shapes) == 2 and shapes[1][0] == "f32":
        kind = "fwd"
    elif len(shapes) == 2:
        kind = "dkv"
    elif len(shapes) == 1:
        kind = "dq"
    else:
        return None
    return kind, int(rows), int(seq), int(dim)
