"""Runs a cell's sets the way the driver's check does: every run a new
process of ``benchmark/run.py``, the same seeds in each set, and per metric
the spread of each set (distance between the first and third quartile, as
``statistics.quantiles(values, n=4)`` gives them, over the median), of the
whole set and of the set less its farthest run.  This
parent never touches JAX, so each child gets the chip.  Result lines go to
``chiprun_out/sets/<cell>.jsonl``.

    python3 benchmark/tools/run_sets.py --workload <cell> --seeds 1,2,3,4,5,6 --sets 2 --seconds 30 [--trace-seed 9]
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def one_run(workload, seed, seconds, trace):
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        cwd=REPO, capture_output=True, text=True)
    lines = [l for l in proc.stdout.splitlines() if l.startswith("{")]
    if proc.returncode != 0 or not lines:
        print(proc.stdout[-3000:], proc.stderr[-3000:], flush=True)
        raise SystemExit(f"run failed: exit {proc.returncode}")
    result = json.loads(lines[-1])
    compared = [json.loads(l) for l in lines[:-1]
                if '"event": "compared"' in l or '"event": "reference"' in l
                or '"event": "window"' in l or '"event": "memory"' in l]
    return {"workload": workload, "seed": seed, "seconds": seconds,
            "trace": trace, "wall_s": time.time() - t0, "result": result,
            "lines": compared}


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def without_farthest(values):
    """The set less the run farthest from its median, which the driver's
    check leaves out when it asks whether a bound is too tight."""
    mid = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - mid))
    return [v for i, v in enumerate(values) if i != far]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace-seed", type=int, default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out_dir = os.path.join(REPO, "chiprun_out", "sets")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{args.workload}.jsonl")
    sets = []
    with open(path, "a") as f:
        for k in range(args.sets):
            rows = []
            for seed in seeds:
                row = {"set": k, **one_run(args.workload, seed, args.seconds, 0)}
                f.write(json.dumps(row) + "\n")
                f.flush()
                r = row["result"]
                print(json.dumps({
                    "set": k, "seed": seed, "correct": r["correct"],
                    "failed": r["failed"], "attempted": r["attempted"],
                    "wall_s": round(row["wall_s"], 1),
                    **{m: v["value"] for m, v in r["metrics"].items()},
                    "compared": {l["number"]: l["value"] for l in row["lines"]
                                 if l.get("event") == "compared"}}),
                    flush=True)
                rows.append(r)
            sets.append(rows)
        if args.trace_seed is not None:
            row = {"set": "trace", **one_run(
                args.workload, args.trace_seed, args.seconds, 1)}
            f.write(json.dumps(row) + "\n")
            print(json.dumps(row["result"]), flush=True)
    for name in sets[0][0]["metrics"]:
        per_set = [[r["metrics"][name]["value"] for r in rows] for rows in sets]
        if len(seeds) >= 2:
            kept = [without_farthest(v) for v in per_set]
            print(json.dumps({
                "metric": name,
                "medians": [statistics.median(v) for v in per_set],
                "spreads": [spread(v) for v in per_set],
                "spreads_without_farthest": [
                    spread(v) if len(v) >= 2 else None for v in kept],
                "ranges_without_farthest": [
                    (max(v) - min(v)) / statistics.median(v) for v in kept],
                "first_runs": [v[0] for v in per_set]}), flush=True)


if __name__ == "__main__":
    main()
