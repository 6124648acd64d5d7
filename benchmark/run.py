#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it takes the cell's chips, builds the system under test from the
seed, warms up the cell's own shapes (set-up), measures for ``--seconds``,
checks what the timed path produced against the plain reference, prints a
line per thing it looked at, and as the LAST line of stdout one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` with ``--trace 1``), and last in it ``compared``: each number
the comparison held against a limit, with that limit.  The same numbers are
the last lines of stderr.  ``--trace 0`` reports the end-to-end
metrics; ``--trace 1`` runs a shorter window under the profiler and reports
the per-layer metrics.  Without an accelerator, with fewer chips than the
cell asks for, or on a device kind that ``peaks.json`` lacks, it exits
non-zero and prints no result.
"""

import time

_STARTED = time.perf_counter()      # set-up is counted from process start

import argparse    # noqa: E402
import json        # noqa: E402
import math        # noqa: E402
import os          # noqa: E402
import sys         # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))


def say(event, **fields):
    print(json.dumps({"event": event, **fields}, default=str), flush=True)


def layer_metrics(cell, context):
    out = {}
    for metric in cell.per_layer:
        value = cell.reader(metric)(context, **metric.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import device as device_lib
    from benchmark.harness import spec
    from benchmark.harness.drivers import DRIVERS

    try:
        cell = spec.load_cell(args.workload)
        devices = device_lib.require_chips(cell.chips)
        peaks = device_lib.load_peaks(devices[0].device_kind)
    except (spec.SpecError, device_lib.DeviceError) as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 2
    say("start", workload=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, device=device_lib.describe(devices),
        compile_cache=device_lib.place_compile_cache())

    compared = {}

    def say_and_keep(event, **fields):
        if event == "compared":
            value = fields["value"]     # no Infinity in the result's JSON
            compared[fields["number"]] = {
                "value": value if math.isfinite(value) else repr(value),
                "limit": fields["limit"]}
        say(event, **fields)

    result = DRIVERS[cell.cell["kind"]](
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices, peaks=peaks, started=_STARTED, say=say_and_keep)

    # memory_peak_bytes is the sum of the two numbers beside it, see
    # harness/device.py: buffers as the runtime read them, scratch as the
    # compiler sized it.
    extra = dict(result["memory"])
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if args.trace:
        from benchmark.harness import profile

        context = {**result["context"], "say": say}
        line["metrics"] = layer_metrics(cell, context)
        prof = context["profile"]
        extra.update(busy_s=prof["busy_s"], window_s=prof["window_s"])
        line["device"] = device_lib.describe(devices, **extra)
        line["breakdown"] = profile.breakdown(prof)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        line["metrics"] = {
            name: {"value": result["end_to_end"][name], "unit": unit}
            for name, unit in units.items()}
        line["device"] = device_lib.describe(devices, **extra)
    line["compared"] = compared
    for number, row in compared.items():
        print(f"compared {number} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
