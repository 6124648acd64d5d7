#!/usr/bin/env python
"""Profile the ResNet-50 bench step and print a roofline summary.

Produces the evidence for or against an "HBM-bandwidth-bound" reading of
the north-star metric:

1. captures a ``jax.profiler`` trace of the hot loop (TensorBoard-viewable
   under --trace_dir),
2. aggregates TensorCore busy time per op category from the xplane proto,
3. reports XLA cost analysis (flops, bytes accessed) against wall clock,
   i.e. achieved TFLOP/s vs achieved GB/s.

Usage: python scripts/profile_resnet.py [--batch 256] [--trace_dir /tmp/rn50]
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


def summarize_xplane(trace_dir: str) -> None:
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
            cats = collections.Counter()
            total = 0
            start, end = None, None
            for ev in line.events:
                name = plane.event_metadata[ev.metadata_id].name
                m = re.match(r"%?([a-zA-Z_\-]+)", name)
                cats[m.group(1) if m else name[:30]] += ev.duration_ps
                total += ev.duration_ps
                o, e = ev.offset_ps, ev.offset_ps + ev.duration_ps
                start = o if start is None else min(start, o)
                end = e if end is None else max(end, e)
            span = (end - start) if start is not None else 0
            print(f"\n[{plane.name}] TensorCore busy {total/1e9:.1f} ms / "
                  f"span {span/1e9:.1f} ms "
                  f"({100*total/max(span,1):.1f}% busy)")
            for k, d in cats.most_common(10):
                print(f"  {d/1e9:8.2f} ms  {100*d/max(total,1):5.1f}%  {k}")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--image_size", type=int, default=224)
    p.add_argument("--trace_dir", default="/tmp/rn50_profile")
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
    wl = get_workload("resnet50", batch_size=args.batch,
                      image_size=args.image_size)
    state, _, train_step, batch_sh = build_state_and_step(
        wl, mesh, precision=BF16, total_steps=args.iters + 10
    )
    it = make_global_batches(
        wl.data_fn(per_host_batch_size(wl.batch_size)),
        batch_sh[wl.example_key],
    )
    b = next(it)
    rng = jax.random.key(0)
    for i in range(5):
        state, _ = train_step(state, b, jax.random.fold_in(rng, i))
    jax.block_until_ready(state.params)

    jax.profiler.start_trace(args.trace_dir)
    t0 = time.perf_counter()
    for i in range(args.iters):
        state, _ = train_step(state, b, jax.random.fold_in(rng, 5 + i))
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0
    jax.profiler.stop_trace()

    step_s = dt / args.iters
    img_s = args.batch / step_s
    print(f"\n{img_s:.1f} img/s  ({step_s*1e3:.1f} ms/step, batch {args.batch})")

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
    bound = "HBM-bandwidth" if gb_s / V5E_PEAK_HBM_GBS > tf_s / V5E_PEAK_BF16_TFLOPS else "compute"
    print(f"=> {bound}-bound")

    summarize_xplane(args.trace_dir)


if __name__ == "__main__":
    main()
