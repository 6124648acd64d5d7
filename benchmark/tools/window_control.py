"""The controls of a window latent layer and of its output gate, on the
chip: the cell's own program with one mechanism wrong, served at the cell's
load and held to the plain reference as a run holds the sound program.
``--mechanism half_window``: the window layers read ``(window + 1) // 2``
positions (257 in 513's place), in a decode step and in a prefill chunk
alike.  ``--mechanism without_gate``: every layer's head-wise output gate
left out (a gate of ones).  A control that the comparison cannot tell from
the sound program is guarded by the CPU tests alone
(``tests/test_dots3_note.py``), and PERF.md has to say so.  One JSON line a
seed, appended to ``chiprun_out/limits/<cell>.window_control.jsonl``, its
reading under the mechanism's name.

    python3 benchmark/tools/window_control.py --workload <cell> --seeds 201,202 [--mechanism without_gate] [--seconds 10]
"""

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def half_window(attention_mask):
    """``attention_mask`` reading half the window it is given."""
    def halved(q_pos, k_pos, window):
        return attention_mask(
            q_pos, k_pos, None if window is None else (window + 1) // 2)

    return halved


def without_gate(mla_output):
    """``mla_output`` of a kind whose gate is left out."""
    def ungated(cfg, sizes, p, xn, ctx):
        return mla_output(cfg, dataclasses.replace(sizes, gated=False), p,
                          xn, ctx)

    return ungated


def half_window_model(module):
    module.attention_mask = half_window(module.attention_mask)


def without_gate_model(module):
    module.mla_output = without_gate(module.mla_output)


MECHANISMS = {
    "half_window": (
        "the window layers read (window + 1) // 2 positions", half_window_model),
    "without_gate": (
        "the head-wise output gates left out (gates of ones)",
        without_gate_model),
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--mechanism", choices=sorted(MECHANISMS),
                    default="half_window")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from benchmark.harness import device as device_lib
    from benchmark.harness import spec
    from benchmark.tools import limits
    from distributed_tensorflow_tpu.models import dots3_note

    cell = spec.load_cell(args.workload)
    devices = device_lib.require_chips(cell.chips)
    device_lib.place_compile_cache()
    said, patch = MECHANISMS[args.mechanism]
    patch(dots3_note)
    out_dir = os.path.join("chiprun_out", "limits")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{cell.name}.window_control.jsonl")
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
