"""95th percentile over the window's requests of one of the client-side
times the benchmark's load generator records (seconds -> ms)."""

from benchmark.harness.stats import percentile


def read(ctx, field):
    values = [getattr(r, field) for r in ctx.get("served", [])]
    values = [v for v in values if v is not None]
    return 1e3 * percentile(values, 95) if values else None
