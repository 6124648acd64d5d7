"""A per-iteration mean of the scheduler's ``stats()``, over the window's
iterations only (the counters are cumulative, so the window's mean is taken
from the snapshots before and after it), as a percentage."""


def read(ctx, key):
    start, end = ctx.get("stats_start"), ctx.get("stats_end")
    if not start or not end:
        return None
    iters = end["iterations"] - start["iterations"]
    if iters <= 0:
        return None
    total = end[key] * end["iterations"] - start[key] * start["iterations"]
    return 100.0 * total / iters
