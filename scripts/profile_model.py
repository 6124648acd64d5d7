#!/usr/bin/env python
"""Profile any workload's train step and print a roofline + op breakdown.

The GPT-2/BERT counterpart of scripts/profile_resnet.py (which owns the
ResNet roofline): captures a ``jax.profiler`` trace
of the hot loop, aggregates TensorCore busy time per op category from the
xplane proto, and reports XLA cost analysis (flops, bytes) against wall
clock.

Usage:
    python scripts/profile_model.py --model=gpt2 --batch_size=16 \
        --flash_attention [--trace_dir /tmp/gpt2_prof]
"""

import argparse
import collections
import glob
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# v5e (TPU v5 lite) per-chip peaks, for the roofline denominators.
V5E_PEAK_BF16_TFLOPS = 197.0
V5E_PEAK_HBM_GBS = 819.0


def summarize_xplane(trace_dir: str, top: int = 14) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
    if not paths:
        print("no xplane found under", trace_dir)
        return
    space = xplane_pb2.XSpace()
    with open(sorted(paths)[-1], "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            # Leaf-only accounting: a scanned model's %while events span
            # their children on the same line, so counting every event
            # double-counts (observed 189% "busy").  An event is a parent
            # iff another event starts inside it.
            evs = sorted(
                ((ev.offset_ps, ev.offset_ps + ev.duration_ps,
                  plane.event_metadata[ev.metadata_id].name)
                 for ev in line.events), key=lambda t: (t[0], -t[1]))
            cats = collections.Counter()
            total = 0
            for i, (o, e, name) in enumerate(evs):
                if i + 1 < len(evs) and evs[i + 1][0] < e:
                    continue  # parent (contains the next event)
                m = re.match(r"%?([a-zA-Z_\-]+[\w\-]*?)(?:[_.]\d+)? =", name)
                key = m.group(1) if m else name.split(" =")[0][:40]
                cats[key] += e - o
                total += e - o
            span = (evs[-1][1] - evs[0][0]) if evs else 0
            print(f"\n[{plane.name}] TensorCore busy {total/1e9:.1f} ms / "
                  f"span {span/1e9:.1f} ms "
                  f"({100*total/max(span,1):.1f}% busy)")
            for k, d in cats.most_common(top):
                print(f"  {d/1e9:8.2f} ms  {100*d/max(total,1):5.1f}%  {k}")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="gpt2")
    p.add_argument("--arch", default=None,
                   help="wide_deep only: wide_deep | dlrm")
    p.add_argument("--batch_size", type=int, default=16)
    p.add_argument("--seq_len", type=int, default=1024)
    p.add_argument("--grad_accum_steps", type=int, default=1)
    p.add_argument("--flash_attention", action="store_true")
    p.add_argument("--trace_dir", default="/tmp/dtt_model_profile")
    p.add_argument("--iters", type=int, default=10)
    args = p.parse_args()

    import jax

    from distributed_tensorflow_tpu import cluster as cluster_lib
    from distributed_tensorflow_tpu import compile_cache
    from distributed_tensorflow_tpu.data import per_host_batch_size
    from distributed_tensorflow_tpu.data.pipeline import make_global_batches
    from distributed_tensorflow_tpu.models import get_workload
    from distributed_tensorflow_tpu.train_lib import build_state_and_step
    from distributed_tensorflow_tpu.training import BF16

    compile_cache.configure()
    mesh = cluster_lib.build_mesh(cluster_lib.MeshConfig(data=1))
    kw = {"arch": args.arch} if args.arch else {}
    wl = get_workload(
        args.model, batch_size=args.batch_size, seq_len=args.seq_len,
        grad_accum_steps=args.grad_accum_steps,
        use_flash_attention=args.flash_attention or None, mesh=mesh, **kw,
    )
    state, _, train_step, batch_sh = build_state_and_step(
        wl, mesh, precision=BF16, grad_accum_steps=args.grad_accum_steps,
        total_steps=args.iters + 10,
    )
    it = make_global_batches(
        wl.data_fn(per_host_batch_size(wl.batch_size)),
        batch_sh[wl.example_key],
    )
    b = next(it)
    rng = jax.random.key(0)
    for i in range(5):
        state, m = train_step(state, b, jax.random.fold_in(rng, i))
    # Scalar-pull fence (see bench.py): fetching a value that depends on
    # the last step bounds the async dispatch queue on any backend.
    jax.device_get(m["loss"])

    jax.profiler.start_trace(args.trace_dir)
    t0 = time.perf_counter()
    for i in range(args.iters):
        state, m = train_step(state, b, jax.random.fold_in(rng, 5 + i))
    jax.device_get(m["loss"])
    dt = time.perf_counter() - t0
    jax.profiler.stop_trace()

    step_s = dt / args.iters
    ex_s = args.batch_size / step_s
    print(f"\n{ex_s:.1f} ex/s, {ex_s*args.seq_len:.0f} tok/s  "
          f"({step_s*1e3:.1f} ms/step, batch {args.batch_size})")

    ca = train_step.lower(state, b, rng).compile().cost_analysis()
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    tf_s = flops / step_s / 1e12
    gb_s = bytes_acc / step_s / 1e9
    print(f"XLA cost analysis: {flops/1e9:.0f} GFLOP, "
          f"{bytes_acc/1e9:.1f} GB accessed per step")
    print(f"achieved: {tf_s:.1f} TFLOP/s "
          f"({100*tf_s/V5E_PEAK_BF16_TFLOPS:.0f}% of v5e bf16 peak), "
          f"{gb_s:.0f} GB/s "
          f"({100*gb_s/V5E_PEAK_HBM_GBS:.0f}% of v5e HBM peak)")
    bound = ("HBM-bandwidth" if gb_s / V5E_PEAK_HBM_GBS >
             tf_s / V5E_PEAK_BF16_TFLOPS else "compute")
    print(f"=> {bound}-bound (by XLA's own cost model; Pallas kernels are "
          "opaque to it — see the xplane breakdown for truth)")

    summarize_xplane(args.trace_dir)


if __name__ == "__main__":
    main()
