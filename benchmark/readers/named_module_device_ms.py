"""Median device time of the launches, inside the traced window, of the
program that the ``XLA Modules`` line shows under ``prefix``: a name the
program gave (``jit_<name>``), not a rule over what the launch contains.
``None`` where no launch carries the name."""

from benchmark.harness import xplane
from benchmark.harness.stats import median


def read(ctx, prefix):
    profile = ctx.get("profile")
    if not profile:
        return None
    trace, window = profile["trace"], profile["window"]
    events = [e for e in xplane.module_events(
        trace.devices[min(trace.devices)], prefix)
        if window[0] <= e.start and e.end <= window[1]]
    return 1e3 * median([e.seconds for e in events]) if events else None
