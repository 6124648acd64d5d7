"""Looks for the moments in which a saturated server delivers nothing.

Offers a serving cell's traffic for ``--seconds`` in one process, with the
program's own span ring on (``obs/trace.py``; no profiler), and afterwards
prints every silence of the token stream longer than ``--silence`` seconds:
when it began, how long it lasted, how late the host woke a sleeping thread
in it (was the process taken off its cores?), the garbage collector's
pauses in it, and the program's spans that overlap it and last longer than
a tenth of it, so that one can see what the scheduler's loop was waiting
for.  Rows go to ``chiprun_out/silence/<cell>.json``.

    python3 benchmark/tools/silence_hunt.py --workload <cell> --seconds 400 --seed 7
"""

import argparse
import gc
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=400.0)
    ap.add_argument("--seed", type=int, default=4242)
    ap.add_argument("--silence", type=float, default=0.4)
    ap.add_argument("--freeze", type=int, choices=(0, 1), default=1,
                    help="collect and freeze set-up's heap first, as a run does")
    args = ap.parse_args(argv)

    from benchmark.harness import device as device_lib
    from benchmark.harness import serve, spec, traffic
    from benchmark.harness.spans import Spans
    from distributed_tensorflow_tpu.obs.trace import default_tracer

    cell = spec.load_cell(args.workload)
    devices = device_lib.require_chips(cell.chips)
    device_lib.place_compile_cache()
    engine, sched, _ = serve.build(cell, args.seed, devices)
    serve.warm_up(cell, sched, args.seed)
    compiles_warm = engine.compile_stats()["compile_total"]

    tracer = default_tracer()
    tracer.enable()
    kept, stop = {}, threading.Event()

    def keep():     # the ring holds some hundred seconds: read it often
        for span in tracer.spans():
            kept[(span[0], span[1], span[3])] = span

    def poll():
        while not stop.wait(5.0):
            keep()

    wakes, pauses, gc_start = [], [], [None]

    def watch(tick=0.02):       # as harness/spans.py: HostStalls, every wake kept
        last = time.perf_counter()
        while not stop.wait(tick):
            now = time.perf_counter()
            if now - last - tick > 0.05:
                wakes.append((last, now))
            last = now

    def on_gc(phase, info):
        if phase == "start":
            gc_start[0] = time.perf_counter()
        elif gc_start[0] is not None:
            pauses.append((gc_start[0], time.perf_counter(),
                           info.get("generation")))

    gc.callbacks.append(on_gc)
    threads = [threading.Thread(target=f, daemon=True) for f in (poll, watch)]
    for t in threads:
        t.start()

    requests = traffic.open_loop_requests(cell.traffic, args.seed, args.seconds)
    if args.freeze:
        gc.collect()
        gc.freeze()
    to_perf = time.perf_counter() - time.monotonic()
    t0 = time.monotonic() + float(cell.traffic["lead_in_s"])
    served = serve.offer(requests, sched, Spans(), t0)
    t_end = time.monotonic()
    serve.withdraw_unstarted(served, sched)
    serve.drain([r for r in served if not r.withdrawn], Spans(), t_end + 60.0)
    stop.set()
    for t in threads:
        t.join()
    keep()
    gc.callbacks.remove(on_gc)
    compiles = engine.compile_stats()["compile_total"] - compiles_warm
    stats = sched.stats()
    sched.close()

    stamps = sorted(t for r in served for t, _ in r.batches if t <= t_end)
    rows = []
    for a, b in zip(stamps, stamps[1:]):
        if b - a < args.silence:
            continue
        lo, hi = a + to_perf, b + to_perf
        over = sorted(
            ({"span": name, "starts_at_s": start - lo, "seconds": end - start,
              "lane": tid, "args": {k: v for k, v in span_args.items()
                                    if isinstance(v, (int, float, str))}}
             for name, start, end, tid, span_args in kept.values()
             if start < hi and end > lo and end - start > (b - a) / 10),
            key=lambda row: -row["seconds"])[:12]
        rows.append({
            "at_s": a - t0, "seconds": b - a,
            "host_woke_late_s": [w1 - w0 for w0, w1 in wakes
                                 if w0 < hi and w1 > lo],
            "gc_pauses_s": [[p1 - p0, gen] for p0, p1, gen in pauses
                            if p0 < hi and p1 > lo and p1 - p0 > 1e-3],
            "spans_over_it": over})
    out = {"workload": cell.name, "seed": args.seed, "seconds": args.seconds,
           "scheduler": cell.cell["scheduler"],
           "rate_per_s": cell.traffic["arrivals"]["rate_per_s"],
           "device": device_lib.describe(devices),
           "tokens": sum(n for r in served for t, n in r.batches if t <= t_end),
           "requests_offered": len(served),
           "compile_post_warmup": int(compiles),
           "queue_depth_at_end": stats.get("queue_depth"),
           "host_woke_late_total_s": sum(w1 - w0 for w0, w1 in wakes),
           "gc_pause_total_s": sum(p1 - p0 for p0, p1, _ in pauses),
           "gc_pause_longest_s": max((p1 - p0 for p0, p1, _ in pauses),
                                     default=0.0),
           "frozen": bool(args.freeze),
           "spans_kept": len(kept), "silences": rows}
    out_dir = os.path.join("chiprun_out", "silence")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{cell.name}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
