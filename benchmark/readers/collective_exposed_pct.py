"""Share of the traced window a chip spent in collectives while no other
operation ran on it, worst chip."""

from benchmark.harness import xplane


def read(ctx):
    profile = ctx.get("profile")
    if not profile:
        return None
    trace, window = profile["trace"], profile["window"]
    per_chip = [xplane.exposed_collective_seconds(lines, window)
                for lines in trace.devices.values()]
    if not any(c["collective_s"] > 0 for c in per_chip):
        return None
    ctx["say"]("collectives", per_chip=per_chip)
    return 100.0 * max(c["exposed_s"] for c in per_chip) / profile["window_s"]
