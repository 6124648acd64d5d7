"""Which launched program is which.

The trace's ``XLA Modules`` line names a launch ``jit_<function>(<id>)``.
The training step is ``jit_step``.  The server's programs are jitted
``functools.partial`` objects, which JAX names ``jit__unknown``: all of
them carry one name, and only the id tells them apart.  Until the program
gives them names of their own (the ``tracing`` issue), a cell's file says
how to tell its programs apart under ``trace_modules``: by a name prefix,
or by a rule over what the launches contain.  ``heaviest`` needs no rule:
the program with the most device time in the window.
"""

from __future__ import annotations

import re
from typing import Dict, List

from benchmark.harness import xplane


def _window_launches(ctx) -> List[xplane.Event]:
    profile = ctx.get("profile")
    if not profile:
        return []
    trace, window = profile["trace"], profile["window"]
    lines = trace.devices[min(trace.devices)]
    return [e for e in xplane.module_events(lines)
            if window[0] <= e.start and e.end <= window[1]]


def by_id(events) -> Dict[str, List[xplane.Event]]:
    out: Dict[str, List[xplane.Event]] = {}
    for e in events:
        out.setdefault(e.name, []).append(e)
    return out


def launches(ctx, module: str) -> List[xplane.Event]:
    events = _window_launches(ctx)
    groups = by_id(events)
    if not groups:
        return []
    if module == "heaviest":
        return max(groups.values(), key=lambda g: sum(e.seconds for e in g))
    rule = ctx["cell"].cell.get("trace_modules", {}).get(module)
    if rule is None:
        return []
    if "prefix" in rule:
        return [e for e in events if e.name.startswith(rule["prefix"])]
    if rule.get("rule") == "contains_op":
        # A program is of this kind if its launches contain an instruction
        # whose text matches the marker, a regular expression (for example
        # an activation of the decode step's shape, slots x 1 x width).
        profile = ctx["profile"]
        ops = profile["trace"].devices[min(profile["trace"].devices)].get(
            xplane.OPS_LINE, [])
        marker = re.compile(rule["marker"])
        marked = [o for o in ops if marker.search(o.name)]
        keep = []
        for name, group in groups.items():
            hit = any(g.start <= o.start and o.end <= g.end
                      for g in group[:3] for o in marked)
            if hit:
                keep.extend(group)
        return keep
    raise ValueError(f"unknown trace_modules rule {rule}")
