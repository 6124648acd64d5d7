"""How far a seed moves a serving cell's window, once, on the chip.  One
process builds the cell's engine and scheduler, then offers the cell's
traffic (lead-in, window, withdrawal and drain as ``harness/serve.py: run``
makes them, without the reference) once for each seed and each
``--shuffle-blocks`` value, each time on the emptied server.  The weights
are those of ``--seed`` throughout: a program whose times do not depend on
the values repeats a traffic seed to the token, so what differs between the
points is what the seed does to the arrivals.  A point says what the server
delivered before and inside the window, second by second, and how deep the
queue stood at both ends.  Where the points of one block size spread by
more than half the bound of ``serve_tokens_per_s``, the window's reading
is the sample of work the seed chose, and the cell will not pass the
driver's check whatever the program does.  Points go to
``chiprun_out/seed_phase/<cell>.json`` and are kept under
``benchmark/records/``.

    python3 benchmark/tools/seed_phase.py --workload <cell> --seeds 1,2,3,4,5 --shuffle-blocks 16,1
"""

import argparse
import copy
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="traffic seeds")
    ap.add_argument("--shuffle-blocks", default=None,
                    help="offer every seed under each of these "
                         "shuffle_block values (default: the mix's own)")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=4242, help="the weights'")
    args = ap.parse_args(argv)

    from benchmark.harness import device as device_lib
    from benchmark.harness import serve, spec, traffic
    from benchmark.harness.spans import HostStalls, Spans

    cell = spec.load_cell(args.workload)
    devices = device_lib.require_chips(cell.chips)
    device_lib.place_compile_cache()
    engine, sched, _ = serve.build(cell, args.seed, devices)
    serve.warm_up(cell, sched, args.seed)
    warm = engine.compile_stats()["compile_total"]
    blocks = ([int(b) for b in args.shuffle_blocks.split(",")]
              if args.shuffle_blocks else [int(cell.traffic["shuffle_block"])])
    points = []
    for block in blocks:
        for seed in (int(s) for s in args.seeds.split(",")):
            mix = copy.deepcopy(cell.traffic)
            mix["shuffle_block"] = block
            requests = traffic.open_loop_requests(mix, seed, args.seconds)
            lead_in = float(mix["lead_in_s"])
            spans = Spans()
            gc.collect()
            gc.freeze()
            served = serve.offer([r for r in requests if r.due_s < 0],
                                 sched, spans, time.monotonic() + lead_in)
            stalls = HostStalls()
            with stalls:
                stats_start = sched.stats()
                t0 = time.monotonic()
                served += serve.offer([r for r in requests if r.due_s >= 0],
                                      sched, spans, t0)
                time.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
                t_end = t0 + args.seconds
                stats_end = sched.stats()
                withdrawn = serve.withdraw_unstarted(served, sched)
            answered = [r for r in served if not r.withdrawn]
            serve.drain(answered, spans,
                        t_end + float(cell.cell["drain_seconds"]))
            gc.unfreeze()
            by_second = [0] * max(1, math.ceil(args.seconds))
            before = 0
            for r in served:
                for t, n in r.batches:
                    if t0 <= t <= t_end:
                        by_second[min(int(t - t0), len(by_second) - 1)] += n
                    elif t < t0:
                        before += n
            point = {
                "shuffle_block": block, "traffic_seed": seed,
                "tokens_per_s": sum(by_second) / args.seconds,
                "tokens_in_window": sum(by_second),
                "tokens_in_lead_in": before,
                "queue_depth_at_open": stats_start.get("queue_depth"),
                "queue_depth_at_close": stats_end.get("queue_depth"),
                "answered": len(answered), "withdrawn_at_close": withdrawn,
                "failed": sum(not r.ok for r in answered),
                "host_stall_s_longest": stalls.longest,
                "host_stall_s_total": stalls.total,
                "tokens_by_second": by_second,
                "compile_post_warmup":
                    engine.compile_stats()["compile_total"] - warm}
            print(json.dumps(point), flush=True)
            points.append(point)
    sched.close()
    out_dir = os.path.join("chiprun_out", "seed_phase")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{cell.name}.json"), "w") as f:
        json.dump({"workload": cell.name, "seconds": args.seconds,
                   "seed": args.seed, "lead_in_s": cell.traffic["lead_in_s"],
                   "scheduler": cell.cell["scheduler"],
                   "device": device_lib.describe(devices),
                   "points": points}, f, indent=1)


if __name__ == "__main__":
    main()
