"""Ring-attention per-block engine bench.

Times ONE ring step's block attention — fwd+bwd, non-causal (the
below-diagonal ring case) — at per-shard sequence lengths the `context`
axis produces at pod scale, comparing the Pallas flash kernel
(`flash_attention_with_lse`, what the ring consumes per block by default)
against the XLA einsum block engine (`_dense_with_lse`, the chunked
fallback's math).  device_get-fenced (see bench.py).

    python scripts/bench_ring_blocks.py [--lens 2048,4096,8192]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--lens", default="2048,4096,8192")
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--head_dim", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_tpu import compile_cache
    from distributed_tensorflow_tpu.ops.flash_attention import (
        _dense_with_lse,
        flash_attention_with_lse,
    )

    compile_cache.configure()

    B, H, D = 1, args.heads, args.head_dim
    scale = 1.0 / float(np.sqrt(D))

    chain = 8  # chained calls per dispatch (amortizes dispatch latency)

    def timed(fn, q, k, v):
        def loss(q, k, v):
            # A scan chain of dependent block-attention calls, backprop
            # through BOTH outputs (out and lse — what the ring's combine
            # does with each block's results).
            def body(carry, _):
                out, lse = fn(carry, k, v)
                nxt = (carry + out.astype(carry.dtype)) * 0.5
                return nxt, jnp.sum(lse)
            # remat the chain links like the production models remat their
            # blocks — without it the einsum engine's (T, T) probs
            # residuals alone are chain x 1 GB at T=4096.
            final, lses = jax.lax.scan(
                jax.checkpoint(body, prevent_cse=False), q, None,
                length=chain)
            return (jnp.sum(final.astype(jnp.float32) ** 2)
                    + jnp.sum(lses))

        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        g = step(q, k, v)
        jax.device_get(g[0].reshape(-1)[0])  # scalar-pull fence
        t0 = time.perf_counter()
        for _ in range(args.iters):
            g = step(q, k, v)
        jax.device_get(g[0].reshape(-1)[0])
        return (time.perf_counter() - t0) / (args.iters * chain) * 1e3

    for T in (int(x) for x in args.lens.split(",")):
        kq = jax.random.key(T)
        q = jax.random.normal(jax.random.fold_in(kq, 1), (B, T, H, D),
                              jnp.bfloat16)
        k = jax.random.normal(jax.random.fold_in(kq, 2), q.shape, q.dtype)
        v = jax.random.normal(jax.random.fold_in(kq, 3), q.shape, q.dtype)
        flash_ms = timed(
            lambda q, k, v: flash_attention_with_lse(
                q, k, v, causal=False, scale=scale), q, k, v)
        dense_ms = timed(
            lambda q, k, v: _dense_with_lse(
                q, k, v, causal=False, scale=scale), q, k, v)
        print(json.dumps({
            "per_shard_T": T, "flash_ms": round(flash_ms, 2),
            "einsum_ms": round(dense_ms, 2),
            "flash_speedup": round(dense_ms / flash_ms - 1, 3),
        }))


if __name__ == "__main__":
    main()
