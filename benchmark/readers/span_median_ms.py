"""Median length of one of the benchmark's host spans."""

from benchmark.harness.stats import median


def read(ctx, span):
    durations = ctx["spans"].durations(span)
    return 1e3 * median(durations) if durations else None
