"""Finds the knee of a serving cell once, on the chip: the highest offered
rate the system sustains.  One process builds the engine and scheduler,
then offers the cell's traffic at each rate for a short window and records
tails and the backlog at the window's end.  The knee is read by eye from
the points (the highest rate whose backlog at window end stays at the size
of a steady queue and whose tails have not taken off); the cell's traffic
file then carries four fifths of it as a number.  Raw points go to
``chiprun_out/knee/<cell>.json`` and are kept under ``benchmark/records/``.

    python3 benchmark/tools/knee_sweep.py --workload <cell> --rates 2,4,6,8 --seconds 20
"""

import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=4242)
    ap.add_argument("--seeds", default=None,
                    help="one traffic seed per rate (default seed+i)")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    from benchmark.harness import device as device_lib
    from benchmark.harness import serve, spec, traffic
    from benchmark.harness.spans import Spans
    from benchmark.harness.stats import percentile

    cell = spec.load_cell(args.workload)
    devices = device_lib.require_chips(cell.chips)
    device_lib.place_compile_cache()
    engine, sched, _ = serve.build(cell, args.seed, devices)
    serve.warm_up(cell, sched, args.seed)
    warm = engine.compile_stats()["compile_total"]
    points = []
    rates = [float(r) for r in args.rates.split(",")]
    seeds = ([int(x) for x in args.seeds.split(",")] if args.seeds
             else [args.seed + i for i in range(len(rates))])
    for i, rate in enumerate(rates):
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"]["rate_per_s"] = rate
        mix["lead_in_s"] = 0.0      # every point starts on an empty server
        requests = traffic.open_loop_requests(mix, seeds[i], args.seconds)
        spans = Spans()
        t0 = time.monotonic()
        served = serve.offer(requests, sched, spans, t0)
        time.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
        t_end = t0 + args.seconds
        backlog = sum(1 for r in served
                      if r.future is not None and not r.future.done())
        serve.drain(served, spans, t_end + 120.0)
        drained = time.monotonic()
        ttft = [r.ttft_s(drained) for r in served]
        tpot = [r.tpot_s for r in served if r.ok and r.tpot_s]
        point = {
            "rate_per_s": rate, "traffic_seed": seeds[i],
            "requests": len(served),
            "failed": sum(not r.ok for r in served),
            "backlog_at_window_end": backlog, "drain_s": drained - t_end,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * percentile(ttft, 95),
            "tpot_p50_ms": 1e3 * percentile(tpot, 50),
            "tpot_p95_ms": 1e3 * percentile(tpot, 95),
            "tokens_per_s_in_window": sum(
                n for r in served for t, n in r.batches if t <= t_end)
            / args.seconds,
            "lateness_p95_ms": 1e3 * percentile(
                [r.lateness_s for r in served], 95),
            "compile_post_warmup":
                engine.compile_stats()["compile_total"] - warm}
        print(json.dumps(point), flush=True)
        points.append(point)
    sched.close()
    out_dir = os.path.join("chiprun_out", "knee")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{cell.name}{args.tag}.json"), "w") as f:
        json.dump({"workload": cell.name, "seconds": args.seconds,
                   "seed": args.seed, "scheduler": cell.cell["scheduler"],
                   "device": device_lib.describe(devices),
                   "points": points}, f, indent=1)


if __name__ == "__main__":
    main()
