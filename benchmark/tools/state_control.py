"""The controls of a recurrent state, on the chip: the cell's own program
with its state wrong in one way, served at the cell's load and held to the
plain reference as a run holds the sound program.  ``--mechanism
without_decay``: the decay left out of the delta rule (``alpha = 1``: a
state that never forgets).  ``--mechanism state_bfloat16``: the state kept
in bfloat16 between positions' launches (what a ``kda_state`` leaf of that
type holds: every decode step and every prefill chunk reads a rounded state
and leaves a rounded one; the arithmetic inside a launch stays float32), the
nearest precision below the float32 the configuration states.  A control
that the comparison cannot tell from the sound program is guarded by the
CPU tests alone, and PERF.md has to say so.  One JSON line a seed, appended
to ``chiprun_out/limits/<cell>.state_control.jsonl``, its reading under the
mechanism's name.

    python3 benchmark/tools/state_control.py --workload <cell> --seeds 201,202 [--mechanism state_bfloat16] [--seconds 10]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def without_decay(project):
    """``kda_project`` with its log decay zeroed."""
    def projected(cfg, p, xn, tail):
        q, k, v, log_decay, *rest = project(cfg, p, xn, tail)
        return (q, k, v, log_decay * 0.0, *rest)

    return projected


def state_rounded(rule):
    """``kda_step`` or ``kda_chunk`` leaving its state as a bfloat16 leaf
    would hold it."""
    def rounded(*args, **kw):
        import jax.numpy as jnp

        out, state = rule(*args, **kw)
        return out, state.astype(jnp.bfloat16).astype(jnp.float32)

    return rounded


def without_decay_model(module):
    module.kda_project = without_decay(module.kda_project)


def state_bfloat16_model(module):
    module.kda_step = state_rounded(module.kda_step)
    module.kda_chunk = state_rounded(module.kda_chunk)


MECHANISMS = {
    "without_decay": (
        "the decay left out of the rule (alpha = 1)", without_decay_model),
    "state_bfloat16": (
        "the state rounded to bfloat16 after every decode step and every "
        "prefill chunk (a kda_state leaf of that type)", state_bfloat16_model),
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--mechanism", choices=sorted(MECHANISMS),
                    default="without_decay")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from benchmark.harness import device as device_lib
    from benchmark.harness import spec
    from benchmark.tools import limits
    from distributed_tensorflow_tpu.models import solar_open2

    cell = spec.load_cell(args.workload)
    devices = device_lib.require_chips(cell.chips)
    device_lib.place_compile_cache()
    said, patch = MECHANISMS[args.mechanism]
    patch(solar_open2)
    out_dir = os.path.join("chiprun_out", "limits")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{cell.name}.state_control.jsonl")
    with open(path, "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            row = limits.serve_seed(cell, seed, devices, False, args.seconds)
            row = {"workload": cell.name, "seed": seed,
                   "mechanism": said,
                   "device": device_lib.describe(devices),
                   "seconds": time.perf_counter() - t0,
                   args.mechanism: row.pop("sound"), **row}
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()


if __name__ == "__main__":
    main()
