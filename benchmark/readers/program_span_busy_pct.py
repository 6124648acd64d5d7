"""Share of the traced window a thread of the program spent inside one of
its own spans (``obs/trace.py``), less the parts that the spans named
under ``less`` cover: a loop's iteration less where it was parked or
blocked on the device is the time its own code took."""

from benchmark.harness import program_spans, xplane


def read(ctx, span, less=()):
    found = program_spans.collect(ctx)
    if not found or span not in found["all"]:
        return None
    window = found["window"]
    busy = xplane.subtract(program_spans.covered(found, [span]),
                           program_spans.covered(found, less))
    return 100.0 * xplane.total(busy) / (window[1] - window[0])
