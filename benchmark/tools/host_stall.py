"""One run of a training cell with a host stall put into its window: the
dispatch loop sleeps ``--stall-s`` seconds once, after window step
``--at-step``, and the cell's ``metrics_every`` is set to
``--metrics-every``.  It shows what a stalled host costs the rate at one
depth of the dispatch queue and at another; the benchmark's own runs never
use it.  The output is that of ``benchmark/run.py``.

    python3 benchmark/tools/host_stall.py --workload <cell> --seed 5 --seconds 30 --stall-s 2.3 --at-step 10 --metrics-every 1
"""

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--stall-s", type=float, required=True)
    ap.add_argument("--at-step", type=int, default=10)
    ap.add_argument("--metrics-every", type=int, required=True)
    args = ap.parse_args(argv)

    from benchmark import run
    from benchmark.harness import spec, train

    load_cell, build = spec.load_cell, train.build

    def cell_at_depth(*a, **kw):
        cell = load_cell(*a, **kw)
        cell.cell["trainer"]["metrics_every"] = args.metrics_every
        return cell

    def build_with_stall(cell, *a, **kw):
        loop, watch, abstract = build(cell, *a, **kw)
        after_step = watch.after_step
        at = int(cell.cell["correct"]["steps"]) + args.at_step

        def stalled(loop, step, metrics):
            if step == at and watch.deadline is not None:
                time.sleep(args.stall_s)
            after_step(loop, step, metrics)

        watch.after_step = stalled
        return loop, watch, abstract

    spec.load_cell, train.build = cell_at_depth, build_with_stall
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "0"])


if __name__ == "__main__":
    sys.exit(main())
