"""Per-model throughput bench (BERT / GPT-2 / wide_deep; the driver's
bench.py owns the ResNet-50 north-star line).

Times the jitted train step on one cached device batch (input excluded, same
contract as bench.py's default mode) and prints one JSON line:

    python scripts/bench_model.py --model=bert --seq_len=128 --batch_size=128
    python scripts/bench_model.py --model=bert --seq_len=512 --batch_size=32 \
        --flash_attention
    python scripts/bench_model.py --model=gpt2 --batch_size=16 \
        --grad_accum_steps=1 --flash_attention

The unit is examples/sec/chip (seq/s for BERT, sequences for GPT-2 — fixed
seq_len makes tok/s = value * seq_len).
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", required=True)
    ap.add_argument("--arch", default=None,
                    help="wide_deep only: wide_deep | dlrm")
    ap.add_argument("--batch_size", type=int, default=128)
    ap.add_argument("--seq_len", type=int, default=128)
    ap.add_argument("--grad_accum_steps", type=int, default=1)
    ap.add_argument("--flash_attention", action="store_true")
    ap.add_argument("--no_flash_attention", action="store_true",
                    help="force flash OFF (absent both flags, the "
                         "workload's own default applies, e.g. BERT's "
                         "per-phase auto)")
    ap.add_argument("--ce_chunk", type=int, default=None,
                    help="gpt2: chunked cross-entropy length (0 = full)")
    ap.add_argument("--table_dtype", choices=("f32", "bf16"), default="f32",
                    help="wide_deep: stored embedding-row dtype (bf16 "
                         "halves gather bytes; f32 master in opt state)")
    ap.add_argument("--emb_dim", type=int, default=None,
                    help="wide_deep: embedding row width (row bytes = "
                         "emb_dim * itemsize vs the ~512B HBM granule)")
    ap.add_argument("--n_positions", type=int, default=None,
                    help="gpt2: position-embedding length (raise above the "
                         "preset's 1024 for the long-context ladder, e.g. "
                         "--n_positions=8192 --seq_len=8192)")
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--windows", type=int, default=3,
                    help="timed windows; reported value is the median, "
                         "spread goes in the JSON (VERDICT r4 weak #1)")
    args = ap.parse_args(argv)

    import jax

    from distributed_tensorflow_tpu import cluster as cluster_lib
    from distributed_tensorflow_tpu import compile_cache
    from distributed_tensorflow_tpu.data import per_host_batch_size
    from distributed_tensorflow_tpu.data.pipeline import make_global_batches
    from distributed_tensorflow_tpu.models import get_workload
    from distributed_tensorflow_tpu.train_lib import build_state_and_step
    from distributed_tensorflow_tpu.training import BF16

    compile_cache.configure()
    n_dev = jax.device_count()
    mesh = cluster_lib.build_mesh(cluster_lib.MeshConfig(data=n_dev))
    kw = {}
    if args.arch:
        kw["arch"] = args.arch
    if args.ce_chunk is not None:
        kw["ce_chunk"] = args.ce_chunk
    if args.table_dtype != "f32":
        kw["table_dtype"] = args.table_dtype
    if args.emb_dim is not None:
        kw["emb_dim"] = args.emb_dim
    if args.n_positions is not None:
        import dataclasses

        from distributed_tensorflow_tpu.models.gpt2 import GPT2Config

        kw["config"] = dataclasses.replace(
            GPT2Config.medium(), n_positions=args.n_positions)
    wl = get_workload(
        args.model,
        batch_size=args.batch_size * n_dev,
        seq_len=args.seq_len,
        grad_accum_steps=args.grad_accum_steps,
        use_flash_attention=(False if args.no_flash_attention
                             else (args.flash_attention or None)),
        mesh=mesh,
        **kw,
    )
    windows = max(1, args.windows)
    state, state_sh, train_step, batch_sh = build_state_and_step(
        wl, mesh, precision=BF16, grad_accum_steps=args.grad_accum_steps,
        total_steps=args.warmup + args.iters * windows,
    )
    host_iter = wl.data_fn(per_host_batch_size(wl.batch_size))
    batch = next(make_global_batches(host_iter, batch_sh[wl.example_key]))
    rng = jax.random.key(0)

    for _ in range(args.warmup):
        state, metrics = train_step(state, batch, rng)
    # Scalar-pull fence (see bench.py): fetching a value that depends on
    # the last step bounds the async dispatch queue on any backend.
    jax.device_get(metrics["loss"])
    jax.device_get(state.step)  # fence covers the param update too (ADVICE r3)
    rates = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(args.iters):
            state, metrics = train_step(state, batch, rng)
        jax.device_get(metrics["loss"])
        jax.device_get(state.step)  # fence covers the param update too
        dt = time.perf_counter() - t0
        rates.append(args.iters * wl.batch_size / dt)

    ex_per_sec = statistics.median(rates)
    print(json.dumps({
        "model": args.model,
        "seq_len": args.seq_len,
        "batch_per_chip": args.batch_size,
        "flash": ("off" if args.no_flash_attention else
                  "on" if args.flash_attention else "workload-default"),
        "table_dtype": args.table_dtype,
        "grad_accum_steps": args.grad_accum_steps,
        "examples_per_sec_per_chip": round(ex_per_sec / n_dev, 1),
        "tokens_per_sec_per_chip": round(ex_per_sec * args.seq_len / n_dev),
        "step_ms": round(1000 * wl.batch_size / ex_per_sec, 2),
        "spread": {
            "n": len(rates),
            "min": round(min(rates) / n_dev, 1),
            "max": round(max(rates) / n_dev, 1),
            # per-window rates enable the same per-window attribution the
            # r5 fence analysis needed from bench.py
            "windows": [round(r / n_dev, 1) for r in rates],
        },
        "loss": float(jax.device_get(metrics["loss"])),
        "devices": n_dev,
    }))


if __name__ == "__main__":
    main()
