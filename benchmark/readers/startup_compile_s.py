"""Seconds of set-up the program spent in the given stages of its compiles
(``trace``: Python to jaxpr, ``lower``: jaxpr to MLIR, ``backend``: XLA's
compile or the read from the persistent cache), from the program's own
``dtt/compile/<stage>`` spans that end before the window opens
(``startup_span_s.collect``).  An inner jitted function's trace lies inside
its outer's, so the seconds are those of the union of one thread's
intervals, summed over the threads that compiled.  The harness's own jitted
helpers are left out (no span of the program was open round them)."""

from benchmark.readers.startup_span_s import collect, seconds


def read(ctx, stages):
    found = collect(ctx)
    if not found:
        return None
    names = {f"dtt/compile/{stage}" for stage in stages}
    by_thread = {}
    for span in found["compiles"]:
        if span[0] in names:
            by_thread.setdefault(span[4].get("thread"), []).append(span)
    if not by_thread:
        return None
    return sum(seconds(spans) for spans in by_thread.values())
