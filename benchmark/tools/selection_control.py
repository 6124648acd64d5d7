"""The control of a learned selection, on the chip: the cell's own program
with the newest ``index_topk`` positions selected in the indexer's place,
served at the cell's load and held to the plain reference as a run holds the
sound program.  A selection that the comparison cannot tell from the
indexer's is guarded by the CPU tests of the selected sets alone, and
PERF.md has to say so.  One JSON line a seed, appended to
``chiprun_out/limits/<cell>.selection_control.jsonl``.

    python3 benchmark/tools/selection_control.py --workload <cell> --seeds 201,202 [--seconds 10]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def newest_mask(scores, k):
    """A prefill chunk's mask: the newest ``k`` positions a query may read
    (those whose score is not -inf), whatever the indexer scored."""
    import jax.numpy as jnp

    valid = scores > -jnp.inf
    behind = jnp.cumsum(valid[..., ::-1], axis=-1, dtype=jnp.int32)[..., ::-1]
    return valid & (behind <= k)


def newest_top(scores, k):
    """A decode step's positions: the row's last ``k`` (a row shorter than
    ``k`` names its first position more than once; the mix has none)."""
    import jax.numpy as jnp

    last = jnp.sum(scores > -jnp.inf, axis=-1, keepdims=True) - 1
    return jnp.maximum(last - jnp.arange(k)[None, :], 0).astype(jnp.int32)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from benchmark.harness import device as device_lib
    from benchmark.harness import spec
    from benchmark.tools import limits
    from distributed_tensorflow_tpu.models import glm_moe_dsa

    cell = spec.load_cell(args.workload)
    devices = device_lib.require_chips(cell.chips)
    device_lib.place_compile_cache()
    glm_moe_dsa.select_mask, glm_moe_dsa.select_top = newest_mask, newest_top
    out_dir = os.path.join("chiprun_out", "limits")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{cell.name}.selection_control.jsonl")
    with open(path, "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            row = limits.serve_seed(cell, seed, devices, False, args.seconds)
            row = {"workload": cell.name, "seed": seed,
                   "selection": "the newest index_topk positions",
                   "device": device_lib.describe(devices),
                   "seconds": time.perf_counter() - t0,
                   "wrong_selection": row.pop("sound"), **row}
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()


if __name__ == "__main__":
    main()
