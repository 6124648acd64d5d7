"""Finds the knee of a serving cell once, on the chip: the highest offered
rate the system sustains.  One process builds the engine and scheduler,
then offers the cell's traffic at each rate for a short window and records
tails and the backlog at the window's end.  The knee is read from
the points (the highest rate whose backlog at window end stays under the
slot count and whose tails have not taken off); a cell below the knee then
carries four fifths of it as its rate, one above it 1.3 times it.
``--num-slots`` sweeps another slot count than the cell's own (the control
of a cell that fills the chip with slots).  Every point starts on an empty
server, so a point above the knee reads less than the capacity over its
whole window: ``slots_full_after_s`` says when nine tenths of the slots
were first live and ``tokens_per_s_second_half`` what the server completed
in the window's second half.
Raw points go to ``chiprun_out/knee/<cell><tag>.json`` and are kept under
``benchmark/records/``.

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


def slots_full_after(served, slots, t0):
    """Seconds from the point's start to the first instant at which nine
    tenths of the slots hold a live request (first token had, last not
    yet; the tenth is what turnover keeps empty); ``None`` if never."""
    slots = -(-9 * slots // 10)
    edges = sorted([(r.batches[0][0], 1) for r in served if r.batches]
                   + [(r.batches[-1][0], -1) for r in served if r.batches])
    live = 0
    for t, step in edges:
        live += step
        if live >= slots:
            return t - t0
    return None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=4242)
    ap.add_argument("--seeds", default=None,
                    help="one traffic seed per rate (default seed+i)")
    ap.add_argument("--num-slots", type=int, default=None,
                    help="sweep this slot count, not the cell's own")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    from benchmark.harness import device as device_lib
    from benchmark.harness import serve, spec, traffic
    from benchmark.harness.spans import Spans
    from benchmark.harness.stats import percentile
    from benchmark.readers import scheduler_stat_pct

    cell = spec.load_cell(args.workload)
    if args.num_slots is not None:
        cell.cell["scheduler"]["num_slots"] = args.num_slots
    slots = int(cell.cell["scheduler"]["num_slots"])
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
        stats_start = sched.stats()
        t0 = time.monotonic()
        served = serve.offer(requests, sched, spans, t0)
        time.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
        t_end = t0 + args.seconds
        stats_end = sched.stats()
        backlog = sum(1 for r in served
                      if r.future is not None and not r.future.done())
        serve.drain(served, spans, t_end + 120.0)
        drained = time.monotonic()
        ttft = [r.ttft_s(drained) for r in served]
        tpot = [r.tpot_s for r in served if r.ok and r.tpot_s]
        half = t0 + args.seconds / 2
        point = {
            "rate_per_s": rate, "traffic_seed": seeds[i], "num_slots": slots,
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
            "tokens_per_s_second_half": sum(
                n for r in served for t, n in r.batches if half < t <= t_end)
            / (args.seconds / 2),
            "slots_full_after_s": slots_full_after(served, slots, t0),
            "slot_occupancy_pct": scheduler_stat_pct.read(
                {"stats_start": stats_start, "stats_end": stats_end},
                "slot_occupancy"),
            "queue_depth_at_window_end": stats_end.get("queue_depth"),
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
