"""Reads the numbers a cell's ``correct`` limits are set from, on the chip.

For each seed, in one process: the program's numbers against the plain
reference (sound runs), and for the first ``--control`` seeds the control's:
the reference computed in float8 in the program's place.  One JSON line a
seed, appended to ``chiprun_out/limits/<cell>.jsonl``.  Training cells need
no measured window; a serving cell gets a short one at its own load.

    python3 benchmark/tools/limits.py --workload <cell> --seeds 101,102,... --control 3 [--seconds 10]
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402


def train_control_only(cell, seed, devices):
    """The control's numbers without the program: both sides references."""
    from benchmark.harness import train

    abstract = train.build_step(cell, devices)[2].params
    exact = train.reference_side(cell, seed, abstract, devices)
    fp8 = train.reference_side(cell, seed, abstract, devices, "fp8")
    huge = {k: float("inf") for k in cell.cell["correct"]["limits"]}
    return {"control": {r["number"]: r["value"] for r in
                        train.compare(fp8, exact, huge)["rows"]},
            "control_losses": fp8["losses"],
            "reference_losses": exact["losses"]}


def train_seed(cell, seed, devices, control):
    from benchmark.harness import train
    from benchmark.harness.spans import Spans

    loop, watch, abstract = train.build(cell, seed, devices, Spans())
    program_side = train.first_steps(cell, seed, loop, watch, abstract)
    loop.state = None
    del loop
    gc.collect()
    exact = train.reference_side(cell, seed, abstract, devices)
    huge = {k: float("inf") for k in cell.cell["correct"]["limits"]}
    out = {"sound": {r["number"]: r["value"] for r in
                     train.compare(program_side, exact, huge)["rows"]},
           "program_losses": program_side["losses"],
           "reference_losses": exact["losses"]}
    if control:
        fp8 = train.reference_side(cell, seed, abstract, devices, "fp8")
        out["control"] = {r["number"]: r["value"] for r in
                          train.compare(fp8, exact, huge)["rows"]}
    return out


def serve_seed(cell, seed, devices, control, seconds):
    """A short window at the cell's own load (lead-in included), then
    every finished request against the reference, as a run compares them."""
    from benchmark.harness import serve, traffic
    from benchmark.harness.spans import Spans

    spans = Spans()
    engine, sched, abstract = serve.build(cell, seed, devices)
    serve.warm_up(cell, sched, seed)
    requests = traffic.open_loop_requests(cell.traffic, seed, seconds)
    t0 = time.monotonic() + float(cell.traffic["lead_in_s"])
    served = serve.offer(requests, sched, spans, t0)
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    withdrawn = serve.withdraw_unstarted(served, sched)
    answered = [r for r in served if not r.withdrawn]
    serve.drain(answered, spans, t0 + seconds + float(cell.cell["drain_seconds"]))
    done = [r for r in answered if r.ok]
    sched.close()
    del sched, engine
    gc.collect()
    prompts = [r.request.prompt for r in done]
    tokens = [r.tokens for r in done]
    worst = lambda gaps: max(float(g.max()) for g in gaps)
    exact = serve.reference_gaps(cell, seed, abstract, prompts, tokens)
    out = {"sound": {"served_logit_gap_max": worst(exact)},
           "offered": len(served), "withdrawn": withdrawn,
           "answered_in_full": len(done),
           "served_tokens": int(sum(len(t) for t in tokens)),
           "program_tokens_off_reference_best": int(sum(
               int((g > 0).sum()) for g in exact))}
    if control:
        low = serve.reference_gaps(cell, seed, abstract, prompts, tokens,
                                   "fp8", pick_own=True)
        out["control"] = {"served_logit_gap_max": worst(
            serve.reference_gaps(cell, seed, abstract, prompts, low))}
        out["control_tokens_differing_from_served"] = int(sum(
            int(np.sum(a != b)) for a, b in zip(low, tokens)))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control-only", action="store_true",
                    help="training cells: read the control alone")
    args = ap.parse_args(argv)

    from benchmark.harness import device as device_lib
    from benchmark.harness import spec

    cell = spec.load_cell(args.workload)
    devices = device_lib.require_chips(cell.chips)
    device_lib.place_compile_cache()
    out_dir = os.path.join("chiprun_out", "limits")
    os.makedirs(out_dir, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    with open(os.path.join(out_dir, f"{cell.name}.jsonl"), "a") as f:
        for i, seed in enumerate(seeds):
            t0 = time.perf_counter()
            control = i < args.control
            if args.control_only:
                row = train_control_only(cell, seed, devices)
            elif cell.cell["kind"] == "train":
                row = train_seed(cell, seed, devices, control)
            else:
                row = serve_seed(cell, seed, devices, control, args.seconds)
            row = {"workload": cell.name, "seed": seed,
                   "device": device_lib.describe(devices),
                   "seconds": time.perf_counter() - t0, **row}
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()


if __name__ == "__main__":
    main()
