"""The least time the chip's memory could take to feed one decode step over
the step's median device time in the traced window, as a percentage, for
any family: the metric's ``args`` name the module under ``harness/`` whose
``decode_step_bytes(shape, **counts)`` reckons the floor's parts
(``bytes_module``) and, for each of its arguments, the two keys of
``ContinuousScheduler.stats()`` whose window mean it is (``counts``:
``{argument: [cumulative average, what it is an average over]}``).  With
``share_of`` (names of the floor's parts) it is instead those parts' share
of the floor, and no traced time enters it.  ``None`` where the program
lacks one of the counters (another family, or a parent commit without it)
or nothing was counted in the window."""

import importlib

from benchmark.harness import modules, program
from benchmark.harness.stats import median
from benchmark.readers.decode_hbm_roofline_pct import _window_mean


def window_cost(ctx, bytes_module, counts):
    """The floor's parts over the traced window's decode steps, with the
    counts they were reckoned from, or None."""
    start, end = ctx.get("stats_start"), ctx.get("stats_end")
    if not start or not end or any(
            key not in stats for pair in counts.values() for key in pair
            for stats in (start, end)):
        return None
    counted = {arg: _window_mean(start, end, key, per)
               for arg, (key, per) in counts.items()}
    if None in counted.values():
        return None
    floor = importlib.import_module(f"benchmark.harness.{bytes_module}")
    return floor.decode_step_bytes(
        program.shape_of(ctx["cell"].config), **counted), counted


def read(ctx, bytes_module, counts, module="decode", per="megastep",
         share_of=None):
    found = window_cost(ctx, bytes_module, counts)
    if found is None:
        return None
    cost, counted = found
    if share_of:
        return 100.0 * sum(cost[part] for part in share_of) / cost["total"]
    events = modules.launches(ctx, module)
    if not events:
        return None
    steps = float(ctx["cell"].cell["scheduler"][per])
    step_s = median([e.seconds for e in events]) / steps
    floor_s = cost["total"] / float(ctx["peaks"]["hbm_bytes_per_s"])
    ctx["say"]("decode_floor", bytes_module=bytes_module,
               step_ms=1e3 * step_s, floor_ms=1e3 * floor_s, bytes=cost,
               **counted)
    return 100.0 * floor_s / step_s
