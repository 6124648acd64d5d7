"""External kernel yardstick: race ops/flash_attention.py against the JAX
in-tree TPU flash attention (jax/experimental/pallas/ops/tpu/
flash_attention.py) at the model shapes (VERDICT r4 next #2 — until now all
flash evidence was self-referential vs this repo's own dense paths).

Method: fwd+bwd (grad of sum(out) w.r.t. q, k AND v) chained through a
``lax.scan`` inside ONE jit per config — per-call dispatch dominates
ms-scale single calls.
The scan feeds each gradient back into its input scaled by 1e-30: enough to
serialize iterations and keep the grads alive (0.0-scaled feedback gets
algebraically folded and the whole backward DCE'd — measured "faster than
hardware peak" before the fix).  Iteration counts grow at small T so device
work dominates the ~10 ms per-call floor.  Each kernel is fed its NATIVE
layout (ours BTHD, in-tree BHTD) — kernel-vs-kernel, no adapter transposes
inside the window.

Masked mode: ours = kv_mask (key-padding, BERT input_mask semantics);
in-tree = SegmentIds emulating the same key padding (padded keys get
segment 1 vs 0 for queries/valid keys).  Dropout is ours-only (the in-tree
kernel has none) and is excluded here.

Prints one JSON line per (T, mode): ours_ms, jax_ms, ratio, and which wins.

    python scripts/bench_flash_vs_jax.py            # full ladder
    python scripts/bench_flash_vs_jax.py --seq 1024 --iters 20
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# (T, B, iters): per-chip batch shrinks as T grows to keep HBM sane; iters
# grow at small T to clear the per-call floor; H/D are the GPT-2-medium /
# BERT head geometry (D=64).
LADDER = [(128, 32, 80), (512, 16, 40), (1024, 8, 20), (4096, 2, 10),
          (8192, 1, 10)]
H, D = 16, 64


def timed_scan(fn, args, iters, windows):
    """Median ms/iter of `fn` chained `iters` times inside one jit."""
    import jax
    import jax.numpy as jnp

    def body(carry, _):
        q, k, v = carry
        dq, dk, dv = fn(q, k, v)
        # Epsilon feedback serializes iterations AND defeats dead-code
        # elimination: 0.0*dq would be algebraically folded to zero and the
        # whole grad computation DCE'd (observed: "13 ms" at T=8192 —
        # above hardware peak).  1e-30 is representable in bf16 (f32
        # exponent range), perturbs values by ~denormals, folds nothing.
        eps = jnp.asarray(1e-30, q.dtype)
        return (q + eps * dq, k + eps * dk, v + eps * dv), ()

    @jax.jit
    def run(q, k, v):
        (q, k, v), _ = jax.lax.scan(body, (q, k, v), None, length=iters)
        return jnp.sum(q[..., 0]) + jnp.sum(k[..., 0]) + jnp.sum(v[..., 0])

    out = run(*args)
    float(jax.device_get(out))  # compile + warm
    rates = []
    for _ in range(windows):
        t0 = time.perf_counter()
        out = run(*args)
        float(jax.device_get(out))  # scalar-pull fence (see bench.py)
        rates.append((time.perf_counter() - t0) * 1000.0 / iters)
    return statistics.median(rates)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=0, help="bench only this T")
    ap.add_argument("--iters", type=int, default=0,
                    help="override the ladder's per-T iteration count")
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--modes", default="causal,full,masked")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu import flash_attention as jfa

    from distributed_tensorflow_tpu import compile_cache
    from distributed_tensorflow_tpu.ops.flash_attention import (
        flash_attention as ours,
    )

    compile_cache.configure()

    ladder = [(t, b, args.iters or i) for t, b, i in LADDER
              if not args.seq or t == args.seq]
    modes = args.modes.split(",")
    rng = np.random.RandomState(0)
    for T, B, iters in ladder:
        qkv_bthd = tuple(
            jnp.asarray(rng.randn(B, T, H, D), jnp.bfloat16) * 0.1
            for _ in range(3)
        )
        qkv_bhtd = tuple(jnp.transpose(x, (0, 2, 1, 3)) for x in qkv_bthd)
        # key-padding mask: last eighth of keys invalid
        valid = (np.arange(T) < T - T // 8)
        kv_mask = jnp.asarray(np.broadcast_to(valid, (B, T)).astype(np.int32))
        seg_q = jnp.zeros((B, T), jnp.int32)
        seg_kv = jnp.asarray(
            np.broadcast_to(~valid, (B, T)).astype(np.int32))
        for mode in modes:
            causal = mode == "causal"

            def ours_step(q, k, v):
                def loss(q, k, v):
                    o = ours(q, k, v, causal=causal,
                             kv_mask=kv_mask if mode == "masked" else None)
                    return jnp.sum(o.astype(jnp.float32))

                return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

            def jax_step(q, k, v):
                def loss(q, k, v):
                    o = jfa.flash_attention(
                        q, k, v,
                        segment_ids=(jfa.SegmentIds(seg_q, seg_kv)
                                     if mode == "masked" else None),
                        causal=causal, sm_scale=1.0 / float(np.sqrt(D)),
                    )
                    return jnp.sum(o.astype(jnp.float32))

                return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

            def make_splash_step():
                # The newer in-tree kernel family.  Masks are static
                # per-head (no per-batch key padding), so only causal/full
                # race it.  sm_scale is applied by scaling q (the kernel
                # has no scale param).
                from jax.experimental.pallas.ops.tpu.splash_attention import (
                    splash_attention_kernel as sk,
                    splash_attention_mask as sm,
                )

                one = (sm.CausalMask((T, T)) if causal
                       else sm.FullMask((T, T)))
                kernel = sk.make_splash_mha(
                    sm.MultiHeadMask([one] * H),
                    head_shards=1, q_seq_shards=1,
                )
                scale = 1.0 / float(np.sqrt(D))

                def step(q, k, v):
                    def loss(q, k, v):
                        o = jax.vmap(kernel)(q * scale, k, v)
                        return jnp.sum(o.astype(jnp.float32))

                    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

                return step

            row = {"T": T, "B": B, "H": H, "D": D, "mode": mode,
                   "iters": iters}
            try:
                row["ours_ms"] = round(
                    timed_scan(ours_step, qkv_bthd, iters,
                               args.windows), 3)
            except Exception as e:  # noqa: BLE001 — report, keep racing
                row["ours_error"] = repr(e)[:200]
            try:
                row["jax_ms"] = round(
                    timed_scan(jax_step, qkv_bhtd, iters,
                               args.windows), 3)
            except Exception as e:  # noqa: BLE001
                row["jax_error"] = repr(e)[:200]
            if mode != "masked":
                try:
                    row["splash_ms"] = round(
                        timed_scan(make_splash_step(), qkv_bhtd, iters,
                                   args.windows), 3)
                except Exception as e:  # noqa: BLE001
                    row["splash_error"] = repr(e)[:200]
            best_ext = min(
                (row[k] for k in ("jax_ms", "splash_ms") if k in row),
                default=None,
            )
            if "ours_ms" in row and best_ext is not None:
                row["ours_over_best_external"] = round(
                    row["ours_ms"] / best_ext, 3)
                row["winner"] = ("ours" if row["ours_ms"] <= best_ext
                                 else "external")
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
