"""The flash attention kernels in the device trace.

``what="roofline_pct"``: the least time the chip could take for the calls
the window made (per call the larger of operations over peak FLOP/s and
bytes over peak bandwidth, from ``harness/flops.py``) over the device time
the calls took.  ``what="time_share_pct"``: the calls' device time over the
device's busy time.  First traced chip."""

from benchmark.harness import flops, program, xplane


def read(ctx, what):
    profile = ctx.get("profile")
    if not profile:
        return None
    trace, window = profile["trace"], profile["window"]
    lines = trace.devices[min(trace.devices)]
    causal = bool(program.shape_of(ctx["cell"].config)["causal"])
    spent = least = 0.0
    bounds = {}
    for event, op in xplane.kernel_calls(lines, window):
        kind = xplane.flash_kind(op["type"])
        if kind is None:
            continue
        name, rows, seq, dim = kind
        floor = flops.least_seconds(
            flops.flash_call_cost(name, rows=rows, seq=seq, head_dim=dim,
                                  causal=causal), ctx["peaks"])
        spent += event.seconds
        least += floor["seconds"]
        bounds[floor["bound"]] = bounds.get(floor["bound"], 0) + 1
    if spent <= 0:
        return None
    ctx["say"]("flash_kernels", calls=sum(bounds.values()), bound_by=bounds,
               device_s=spent, least_s=least)
    if what == "roofline_pct":
        return 100.0 * least / spent
    busy = xplane.total(xplane.clip(xplane.busy_intervals(lines), window))
    return 100.0 * spent / busy
