"""Median length of one of the program's own spans (``obs/trace.py``),
over those that end inside the traced window."""

from benchmark.harness import program_spans
from benchmark.harness.stats import median


def read(ctx, span):
    found = program_spans.collect(ctx)
    events = found["ended"].get(span) if found else None
    if not events:
        return None
    return 1e3 * median([event.seconds for event, _ in events])
