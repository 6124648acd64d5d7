"""Device time of one kind of jitted program over the device's busy time
in the traced window, first traced chip."""

from benchmark.harness import modules, xplane


def read(ctx, module):
    events = modules.launches(ctx, module)
    profile = ctx.get("profile")
    if not events or not profile:
        return None
    trace, window = profile["trace"], profile["window"]
    lines = trace.devices[min(trace.devices)]
    busy = xplane.total(xplane.clip(xplane.busy_intervals(lines), window))
    return 100.0 * sum(e.seconds for e in events) / busy if busy > 0 else None
