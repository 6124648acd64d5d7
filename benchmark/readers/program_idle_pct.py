"""Share of the traced window in which the first traced chip ran nothing
and the program was not inside ``span`` (its own, ``obs/trace.py``): with
the scheduler's parked wait as ``span``, the device's idle time while
there was work to do."""

from benchmark.harness import program_spans, xplane


def read(ctx, span):
    found = program_spans.collect(ctx)
    if not found:
        return None
    window = found["window"]
    trace = ctx["profile"]["trace"]
    gaps = xplane.idle_gaps(trace.devices[min(trace.devices)], window)
    left = xplane.subtract(gaps, program_spans.covered(found, [span]))
    return 100.0 * xplane.total(left) / (window[1] - window[0])
