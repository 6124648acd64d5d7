"""Scaling-efficiency harness (the north-star metric's scaled half:
"images/sec/chip; scaling efficiency 8→256 chips", BASELINE.json).

Runs ResNet-50 data-parallel at every mesh width the available devices
allow, reports images/sec/chip per width and efficiency vs the 1-chip
number.  On real pod hardware (jax.device_count() = 8/64/256) the numbers
are the real scaling curve; on a single chip only width 1 runs, and on the
virtual CPU mesh the curve is a *structural* check (collectives execute,
efficiency numbers are not hardware-meaningful — labeled as such, per
SURVEY.md §8 "measuring 8→256 scaling without a pod").

Usage: python scripts/scaling_bench.py [--per-chip-batch 256] [--iters 15]
Output: one JSON line per mesh width + a summary line.
"""

import argparse
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--per-chip-batch", type=int, default=None)
    ap.add_argument("--iters", type=int, default=15)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--input", choices=("cached", "loader"), default="cached",
                    help="loader: feed every width through the real input "
                         "path (staged records -> native loader -> device "
                         "prefetch) instead of one cached batch")
    ap.add_argument("--records", type=int, default=1024)
    ap.add_argument("--data_dir", default="/tmp/dtt_bench_data")
    args = ap.parse_args()

    import jax

    from distributed_tensorflow_tpu import cluster as cluster_lib
    from distributed_tensorflow_tpu.data import per_host_batch_size
    from distributed_tensorflow_tpu.data.pipeline import make_global_batches
    from distributed_tensorflow_tpu.models import get_workload
    from distributed_tensorflow_tpu.train_lib import build_state_and_step
    from distributed_tensorflow_tpu.training import BF16

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    per_chip = args.per_chip_batch or (256 if on_tpu else 8)
    image, stages = (224, (3, 4, 6, 3)) if on_tpu else (32, (1, 1, 1, 1))

    n_total = jax.device_count()
    widths = [w for w in (1, 2, 4, 8, 16, 32, 64, 128, 256)
              if w <= n_total and n_total % w == 0]

    results = {}
    for width in widths:
        devices = jax.devices()[:width]
        mesh = cluster_lib.build_mesh(
            cluster_lib.MeshConfig(data=width), devices
        )
        wl = get_workload(
            "resnet50", batch_size=per_chip * width,
            image_size=image, stage_sizes=stages,
        )
        state, _, step, bsh = build_state_and_step(
            wl, mesh, precision=BF16, total_steps=args.warmup + args.iters,
        )
        if args.input == "loader":
            import os

            from distributed_tensorflow_tpu.data.pipeline import (
                DevicePrefetchIterator,
            )
            from distributed_tensorflow_tpu.data.records import (
                record_data_fn,
                resolve_or_stage,
            )

            paths = resolve_or_stage(args.data_dir, wl, args.records)
            data_iter = iter(DevicePrefetchIterator(
                record_data_fn(paths, wl, num_threads=2, prefetch=4)(
                    per_host_batch_size(wl.batch_size)),
                bsh[wl.example_key], prefetch=2,
            ))
        else:
            import itertools

            it = make_global_batches(
                wl.data_fn(per_host_batch_size(wl.batch_size)),
                bsh[wl.example_key],
            )
            data_iter = itertools.repeat(next(it))
        rng = jax.random.key(0)
        for i in range(args.warmup):
            state, m = step(state, next(data_iter), jax.random.fold_in(rng, i))
        if args.warmup:
            # Scalar-pull fence (see bench.py): fetching a value that
            # depends on the last step bounds the async dispatch queue.
            jax.device_get(m["loss"])
            jax.device_get(state.step)  # fence covers the update (ADVICE r3)
        t0 = time.perf_counter()
        for i in range(args.iters):
            state, m = step(state, next(data_iter),
                            jax.random.fold_in(rng, 99 + i))
        jax.device_get(m["loss"])
        jax.device_get(state.step)  # fence covers the update (ADVICE r3)
        dt = time.perf_counter() - t0
        close = getattr(data_iter, "close", None)
        if callable(close):
            close()  # stop the prefetch thread; free pinned device batches
        del data_iter
        ips = wl.batch_size * args.iters / dt
        results[width] = ips / width
        print(json.dumps({
            "mesh_width": width,
            "images_per_sec_per_chip": round(ips / width, 2),
            "images_per_sec_total": round(ips, 2),
            "platform": platform,
        }))

    base = results.get(1)
    summary = {
        "metric": ("resnet50_scaling_efficiency" if args.input == "cached"
                   else "resnet50_scaling_efficiency_loader_fed"),
        "platform": platform,
        "hardware_meaningful": bool(on_tpu and n_total > 1),
        "per_chip_batch": per_chip,
        "efficiency_vs_1chip": {
            str(w): round(v / base, 4) for w, v in results.items()
        } if base else {},
    }
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
