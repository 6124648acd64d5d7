"""Real-TPU validation of the Pallas flash-attention kernels: numerics vs
the dense XLA path on the chip (not interpreted), plus a timing loop.

Run on a machine with a TPU:  python scripts/validate_tpu.py
(not a pytest — the pytest suite pins JAX to the virtual CPU mesh).

``chip_smoke.py`` calls the three ``validate_*`` functions (everything here
except ``time_kernels``): they are the only place the in-kernel dropout PRNG
executes, since it has no interpreter lowering.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp


def _inputs(B, T, H, D, seed=0, scale=0.3):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * scale)
                 for _ in range(3))


def _max_err(got, want):
    # Explicit fetch point (dttlint host-sync): one device_get per config.
    return float(jax.device_get(jnp.max(jnp.abs(
        got.astype(jnp.float32) - want.astype(jnp.float32)))))


def _grad_rel_errs(loss_flash, loss_dense, args):
    """max |g_flash - g_dense| / max |g_dense| per argument (gradient sums
    over T accumulate magnitude, so the error is relative to its scale)."""
    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(*args)
    g_dense = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(*args)
    rels = []
    for gf, gd in zip(g_flash, g_dense):
        denom = float(jnp.max(jnp.abs(gd.astype(jnp.float32)))) or 1.0
        rels.append(_max_err(gf, gd) / denom)
    return rels


def validate_parity():
    """Forward (f32, bf16; causal or not) and backward (bf16 causal) of the
    kernel against ``_dense`` on the chip."""
    assert jax.devices()[0].platform == "tpu", jax.devices()
    from distributed_tensorflow_tpu.ops import flash_attention
    from distributed_tensorflow_tpu.ops.flash_attention import _dense

    B, T, H, D = 4, 2048, 8, 64
    scale = 1 / np.sqrt(D)
    q, k, v = _inputs(B, T, H, D)

    for causal in (False, True):
        got = jax.jit(
            lambda a, b, c: flash_attention(a, b, c, causal=causal)
        )(q, k, v)
        want = jax.jit(
            lambda a, b, c: _dense(a, b, c, causal=causal, scale=scale)
        )(q, k, v)
        err = _max_err(got, want)
        print(f"causal={causal}: max_abs_err={err:.3e}")
        # f32 matmuls on the MXU run as bf16 multi-pass by default, in both
        # paths but with different blockings — ~1e-3 is the expected noise.
        assert err < 5e-3, err

    # bf16 path (the production dtype)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    errb = _max_err(
        jax.jit(lambda a, b, c: flash_attention(a, b, c, causal=True))(
            qb, kb, vb),
        jax.jit(lambda a, b, c: _dense(a, b, c, causal=True, scale=scale))(
            qb, kb, vb))
    print(f"bf16 causal: max_abs_err={errb:.3e}")
    assert errb < 3e-2, errb

    # Gradient parity: the Pallas dq/dk/dv kernels vs XLA autodiff of the
    # dense formulation (bf16 production dtype, causal).
    rels = _grad_rel_errs(
        lambda a, b, c: flash_attention(
            a, b, c, causal=True).astype(jnp.float32).sum(),
        lambda a, b, c: _dense(
            a, b, c, causal=True, scale=scale).astype(jnp.float32).sum(),
        (qb, kb, vb))
    for nm, rel in zip("qkv", rels):
        print(f"grad d{nm}: max_rel_err={rel:.3e}")
        assert rel < 5e-2, (nm, rel)


def validate_kv_mask():
    """Key-padding mask (BERT ``input_mask``) at the BERT-base shape: the
    non-causal kernel forward and backward against ``_dense``, bf16, with
    ragged valid lengths (one row fully valid, one cut mid-block)."""
    assert jax.devices()[0].platform == "tpu", jax.devices()
    from distributed_tensorflow_tpu.ops import flash_attention
    from distributed_tensorflow_tpu.ops.flash_attention import _dense

    B, T, H, D = 8, 512, 12, 64
    scale = 1 / np.sqrt(D)
    q, k, v = (x.astype(jnp.bfloat16) for x in _inputs(B, T, H, D, seed=3))
    lengths = np.array([512, 400, 257, 128, 511, 64, 300, 1])
    mask = jnp.asarray(
        (np.arange(T)[None, :] < lengths[:, None]).astype(np.int32))

    def flash(a, b, c):
        return flash_attention(a, b, c, causal=False, kv_mask=mask)

    def dense(a, b, c):
        return _dense(a, b, c, causal=False, scale=scale, kv_mask=mask)

    err = _max_err(jax.jit(flash)(q, k, v), jax.jit(dense)(q, k, v))
    print(f"kv_mask bf16 (B,T,H,D)={(B, T, H, D)}: max_abs_err={err:.3e}")
    assert err < 3e-2, err
    rels = _grad_rel_errs(
        lambda a, b, c: flash(a, b, c).astype(jnp.float32).sum(),
        lambda a, b, c: dense(a, b, c).astype(jnp.float32).sum(),
        (q, k, v))
    for nm, rel in zip("qkv", rels):
        print(f"kv_mask grad d{nm}: max_rel_err={rel:.3e}")
        assert rel < 5e-2, (nm, rel)


def time_kernels():
    """Wall-clock of the bf16 causal forward, flash vs dense (includes
    dispatch latency; device times are the benchmark's, from its
    trace)."""
    from distributed_tensorflow_tpu.ops import flash_attention
    from distributed_tensorflow_tpu.ops.flash_attention import _dense

    B, T, H, D = 4, 2048, 8, 64
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in _inputs(B, T, H, D))
    f_flash = jax.jit(lambda a, b, c: flash_attention(a, b, c, causal=True))
    f_dense = jax.jit(
        lambda a, b, c: _dense(a, b, c, causal=True, scale=1 / np.sqrt(D))
    )
    for name, fn in (("flash", f_flash), ("dense", f_dense)):
        fn(qb, kb, vb).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(qb, kb, vb)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / 20
        flops = 4 * B * H * T * T * D / 2  # causal half
        print(f"{name}: {dt * 1e3:.2f} ms/iter  "
              f"{flops / dt / 1e12:.2f} TFLOP/s (wall-clock incl. dispatch)")


def main():
    validate_parity()
    validate_kv_mask()
    time_kernels()
    validate_kernel_dropout()
    print("TPU validation OK")


def validate_kernel_dropout():
    """In-kernel PRNG attention dropout (the only place it executes — the
    interpreter has no prng_seed lowering, so CI covers just the dense
    fallback).  Checks: determinism per seed, variation across seeds,
    unbiasedness of the keep/(1-rate) rescale, EXACT fwd/bwd mask agreement
    (extracted via v=I), and VJP-vs-finite-difference gradients at highest
    matmul precision (default f32 MXU precision is bf16-passes — FD noise
    swamps the check otherwise; measured rel-err 0.5 at default, 2e-4 at
    highest)."""
    from distributed_tensorflow_tpu.ops import flash_attention

    B, T, H, D = 1, 512, 4, 64
    r = np.random.RandomState(0)
    mk = lambda: jnp.asarray(r.randn(B, T, H, D).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    rng1 = jax.random.key(1)

    a = np.asarray(flash_attention(q, k, v, causal=False, dropout_rate=0.3,
                                   dropout_rng=rng1))
    b = np.asarray(flash_attention(q, k, v, causal=False, dropout_rate=0.3,
                                   dropout_rng=rng1))
    c = np.asarray(flash_attention(q, k, v, causal=False, dropout_rate=0.3,
                                   dropout_rng=jax.random.key(2)))
    assert np.array_equal(a, b), "dropout not deterministic per seed"
    assert not np.allclose(a, c), "dropout identical across seeds"
    print("dropout: deterministic per seed, varies across seeds")

    # Exact fwd/bwd mask agreement: T=D so v=I reads the dropped prob
    # matrix out of the forward, and g=I reads it out of dV.
    Tm = 128
    qz = jnp.zeros((1, Tm, 1, Tm), jnp.float32)  # equal scores: P = 1/T
    eye = jnp.eye(Tm, dtype=jnp.float32).reshape(1, Tm, 1, Tm)
    rate = 0.25
    out = flash_attention(qz, qz, eye, causal=False, dropout_rate=rate,
                          dropout_rng=rng1)
    M_fwd = np.asarray(out).reshape(Tm, Tm) * Tm * (1 - rate)
    _, vjp = jax.vjp(
        lambda v_: flash_attention(qz, qz, v_, causal=False,
                                   dropout_rate=rate, dropout_rng=rng1),
        eye)
    (dv,) = vjp(eye)
    M_bwd = np.asarray(dv).reshape(Tm, Tm).T * Tm * (1 - rate)
    assert np.allclose(M_fwd, M_bwd, atol=1e-4), "fwd/bwd masks differ"
    keep = (M_fwd > 0.5).mean()
    assert abs(keep - (1 - rate)) < 0.05, f"keep fraction {keep} vs {1-rate}"
    print(f"dropout: fwd/bwd masks identical, keep fraction {keep:.3f}")

    # Unbiasedness: E[dropped out] == undropped out.
    base = np.asarray(flash_attention(q, k, v, causal=False))
    acc = np.zeros_like(base)
    n = 32
    for s in range(n):
        acc += np.asarray(flash_attention(
            q, k, v, causal=False, dropout_rate=rate,
            dropout_rng=jax.random.key(100 + s)))
    rel = np.abs(acc / n - base).max() / np.abs(base).max()
    assert rel < 0.2, f"dropout mean deviates {rel:.3f}"
    print(f"dropout: mean-vs-undropped rel err over {n} seeds {rel:.3f}")

    # Gradients: VJP vs central finite difference, fixed seed.
    with jax.default_matmul_precision("highest"):
        w = jnp.asarray(np.random.RandomState(5).randn(*q.shape)
                        .astype(np.float32))
        rngg = jax.random.key(7)

        def f(q_, k_, v_):
            o = flash_attention(q_, k_, v_, causal=True, dropout_rate=0.2,
                                dropout_rng=rngg)
            return jnp.sum(o * w)

        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        rs = np.random.RandomState(6)
        for idx, gx in enumerate(g):
            d = jnp.asarray(rs.randn(*q.shape).astype(np.float32))
            eps = 1e-2
            args = [q, k, v]
            ap = list(args); ap[idx] = args[idx] + eps * d
            am = list(args); am[idx] = args[idx] - eps * d
            fd = float(f(*ap) - f(*am)) / (2 * eps)
            an = float(jnp.sum(gx * d))
            rel = abs(fd - an) / max(abs(an), 1e-6)
            print(f"dropout grad arg{idx}: fd={fd:.4f} vjp={an:.4f} "
                  f"rel={rel:.2e}")
            assert rel < 5e-3, (idx, fd, an)


if __name__ == "__main__":
    sys.exit(main())
