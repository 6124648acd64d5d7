"""Flash-attention kernel tests.

The Pallas lowering itself is TPU-only; on CPU the kernel logic runs in the
Pallas interpreter (DTT_PALLAS_INTERPRET=1) and must match dense attention
exactly.  The real-TPU numerics check runs in scripts/validate_tpu.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def make_qkv(B=2, T=256, H=2, D=32, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(dtype))
    return mk(), mk(), mk()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_interpret_kernel_matches_dense(self, monkeypatch, causal):
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
        from distributed_tensorflow_tpu.ops import flash_attention
        from distributed_tensorflow_tpu.ops.flash_attention import _dense

        q, k, v = make_qkv()
        got = flash_attention(q, k, v, causal=causal)
        want = _dense(q, k, v, causal=causal, scale=1 / np.sqrt(q.shape[-1]))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_multi_block_causal(self, monkeypatch):
        # T=512 with 128-blocks -> 4 q-blocks x 4 k-blocks; exercises the
        # causal block-skip bounds and the online-softmax rescale (blocks
        # pinned: the production default 512 would clamp to single-block)
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
        import importlib

        fa_mod = importlib.import_module(
            "distributed_tensorflow_tpu.ops.flash_attention")
        monkeypatch.setattr(fa_mod, "BLOCK_Q", 128)
        monkeypatch.setattr(fa_mod, "BLOCK_K", 128)
        from distributed_tensorflow_tpu.ops import flash_attention
        from distributed_tensorflow_tpu.ops.flash_attention import _dense

        q, k, v = make_qkv(B=1, T=512, H=1, D=16, seed=3)
        got = flash_attention(q, k, v, causal=True)
        want = _dense(q, k, v, causal=True, scale=1 / np.sqrt(16))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_fit_block_keeps_128_multiples_supported(self):
        """Raising the default blocks to 512 must not drop seq lens that
        are multiples of 128 but not 512 (640/768/1152...) off the flash
        path — _fit_block falls back to the largest dividing block."""
        import importlib

        fa_mod = importlib.import_module(
            "distributed_tensorflow_tpu.ops.flash_attention")
        assert fa_mod._fit_block(1024, 512) == 512
        assert fa_mod._fit_block(768, 512) == 384
        assert fa_mod._fit_block(640, 512) == 128
        assert fa_mod._fit_block(1152, 512) == 384
        assert fa_mod._fit_block(96, 512) == 96  # T <= want: whole seq
        assert fa_mod._fit_block(130, 512) is None  # no 128-divisor

    def test_cpu_fallback_without_interpret(self, monkeypatch):
        monkeypatch.delenv("DTT_PALLAS_INTERPRET", raising=False)
        from distributed_tensorflow_tpu.ops import flash_attention
        from distributed_tensorflow_tpu.ops.flash_attention import _dense

        q, k, v = make_qkv(T=48)  # non-block-aligned: dense path either way
        got = flash_attention(q, k, v, causal=True)
        want = _dense(q, k, v, causal=True, scale=1 / np.sqrt(q.shape[-1]))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)

    def test_gradients_flow(self, monkeypatch):
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
        from distributed_tensorflow_tpu.ops import flash_attention
        from distributed_tensorflow_tpu.ops.flash_attention import _dense

        q, k, v = make_qkv(B=1, T=128, H=1, D=16, seed=5)

        g_flash = jax.grad(
            lambda q_: jnp.sum(flash_attention(q_, k, v, causal=True) ** 2)
        )(q)
        g_dense = jax.grad(
            lambda q_: jnp.sum(_dense(q_, k, v, causal=True,
                                      scale=1 / np.sqrt(16)) ** 2)
        )(q)
        np.testing.assert_allclose(np.asarray(g_flash), np.asarray(g_dense),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_fused_backward_matches_dense(self, monkeypatch, causal):
        """dq/dk/dv from the Pallas backward kernels vs XLA autodiff of the
        dense formulation — multi-block (T=384 -> 3x3 128-tiles; blocks
        pinned so the fori_loop bounds and accumulators really iterate)."""
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
        import importlib

        fa_mod = importlib.import_module(
            "distributed_tensorflow_tpu.ops.flash_attention")
        monkeypatch.setattr(fa_mod, "BLOCK_Q", 128)
        monkeypatch.setattr(fa_mod, "BLOCK_K", 128)
        from distributed_tensorflow_tpu.ops import flash_attention
        from distributed_tensorflow_tpu.ops.flash_attention import _dense

        q, k, v = make_qkv(B=2, T=384, H=2, D=16, seed=7)
        g = jnp.asarray(
            np.random.RandomState(11).randn(*q.shape).astype(np.float32))

        def run(fn):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(g)

        scale = 1 / np.sqrt(q.shape[-1])
        got = run(lambda q_, k_, v_: flash_attention(q_, k_, v_,
                                                     causal=causal))
        want = run(lambda q_, k_, v_: _dense(q_, k_, v_, causal=causal,
                                             scale=scale))
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                err_msg=f"{name} mismatch (causal={causal})",
            )

    @pytest.mark.parametrize("causal", [False, True])
    def test_kv_mask_matches_dense_fwd_and_bwd(self, monkeypatch, causal):
        """Key padding mask (BERT input_mask semantics) through the fused
        kernels: fwd + dq/dk/dv vs XLA autodiff of masked dense.  Multi-
        block so masked keys land in interior tiles, with one row masked
        below a block boundary."""
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
        import importlib

        fa_mod = importlib.import_module(
            "distributed_tensorflow_tpu.ops.flash_attention")
        monkeypatch.setattr(fa_mod, "BLOCK_Q", 128)
        monkeypatch.setattr(fa_mod, "BLOCK_K", 128)
        from distributed_tensorflow_tpu.ops import flash_attention
        from distributed_tensorflow_tpu.ops.flash_attention import _dense

        q, k, v = make_qkv(B=2, T=384, H=2, D=16, seed=13)
        lens = np.array([300, 100])  # one crosses a 128-block boundary
        mask = jnp.asarray(
            (np.arange(384)[None, :] < lens[:, None]).astype(np.int32))
        g = jnp.asarray(
            np.random.RandomState(17).randn(*q.shape).astype(np.float32))
        scale = 1 / np.sqrt(q.shape[-1])

        def run(fn):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(g)

        got = run(lambda q_, k_, v_: flash_attention(
            q_, k_, v_, causal=causal, kv_mask=mask))
        want = run(lambda q_, k_, v_: _dense(
            q_, k_, v_, causal=causal, scale=scale, kv_mask=mask))
        for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                err_msg=f"{name} mismatch (causal={causal})",
            )

    @pytest.mark.parametrize("causal", [False, True])
    def test_with_lse_matches_dense_and_lse_cotangent(self, monkeypatch,
                                                      causal):
        """flash_attention_with_lse: (out, lse) parity AND gradient parity
        when the loss consumes BOTH outputs (the lse cotangent feeds the
        dS = P(dP - Δ + g_lse) term ring attention's combine depends on)."""
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
        import importlib

        fa_mod = importlib.import_module(
            "distributed_tensorflow_tpu.ops.flash_attention")
        monkeypatch.setattr(fa_mod, "BLOCK_Q", 128)
        monkeypatch.setattr(fa_mod, "BLOCK_K", 128)
        from distributed_tensorflow_tpu.ops.flash_attention import (
            _dense_with_lse,
            flash_attention_with_lse,
        )

        q, k, v = make_qkv(B=2, T=256, H=2, D=16, seed=19)
        rng = np.random.RandomState(23)
        wo = jnp.asarray(rng.randn(*q.shape).astype(np.float32))
        wl = jnp.asarray(rng.randn(2, 2, 256).astype(np.float32))
        scale = 1 / np.sqrt(q.shape[-1])

        out, lse = flash_attention_with_lse(q, k, v, causal=causal)
        ro, rl = _dense_with_lse(q, k, v, causal=causal, scale=scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ro),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(rl),
                                   rtol=2e-5, atol=2e-5)

        def loss(fn):
            def f(q_, k_, v_):
                o, l = fn(q_, k_, v_)
                return jnp.sum(o * wo) + jnp.sum(l * wl)
            return f

        got = jax.grad(loss(lambda *xs: flash_attention_with_lse(
            *xs, causal=causal)), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(lambda *xs: _dense_with_lse(
            *xs, causal=causal, scale=scale)), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip(("dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                err_msg=f"{name} mismatch (causal={causal})",
            )

    def test_fused_backward_bf16(self, monkeypatch):
        """bf16 inputs (the training dtype): kernels accumulate f32, so the
        result should track the dense-bf16 path within bf16 tolerance."""
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
        import importlib

        fa_mod = importlib.import_module(
            "distributed_tensorflow_tpu.ops.flash_attention")
        monkeypatch.setattr(fa_mod, "BLOCK_Q", 128)
        monkeypatch.setattr(fa_mod, "BLOCK_K", 128)
        from distributed_tensorflow_tpu.ops import flash_attention
        from distributed_tensorflow_tpu.ops.flash_attention import _dense

        q, k, v = make_qkv(B=1, T=256, H=2, D=16, seed=9)
        q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
        scale = 1 / np.sqrt(16)

        def loss(fn, *xs):
            return jnp.sum(fn(*xs).astype(jnp.float32) ** 2)

        got = jax.grad(
            lambda q_: loss(lambda a: flash_attention(a, k, v, causal=True),
                            q_))(q)
        want = jax.grad(
            lambda q_: loss(
                lambda a: _dense(a, k, v, causal=True, scale=scale), q_))(q)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=0.1, atol=0.1,
        )


class TestFlashDropout:
    """In-kernel attention-probability dropout (VERDICT r3 #6): the flash
    path must not silently change the training recipe vs dense."""

    def test_rate_zero_is_exact(self, monkeypatch):
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
        from distributed_tensorflow_tpu.ops import flash_attention

        q, k, v = make_qkv(seed=11)
        base = flash_attention(q, k, v, causal=True)
        zero = flash_attention(q, k, v, causal=True, dropout_rate=0.0)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(zero))

    def test_deterministic_per_seed_and_varies_across_seeds(self, monkeypatch):
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
        from distributed_tensorflow_tpu.ops import flash_attention

        q, k, v = make_qkv(seed=12)
        r1 = jax.random.key(1)
        a = flash_attention(q, k, v, causal=False, dropout_rate=0.3,
                            dropout_rng=r1)
        b = flash_attention(q, k, v, causal=False, dropout_rate=0.3,
                            dropout_rng=r1)
        c = flash_attention(q, k, v, causal=False, dropout_rate=0.3,
                            dropout_rng=jax.random.key(2))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.allclose(np.asarray(a), np.asarray(c))

    def test_dropout_is_unbiased(self, monkeypatch):
        # E[dropped attention out] == undropped out (keep/(1-rate) rescale,
        # softmax denominator sees undropped p). Average over many seeds.
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
        from distributed_tensorflow_tpu.ops import flash_attention

        q, k, v = make_qkv(B=1, T=128, H=1, D=32, seed=13)
        want = np.asarray(flash_attention(q, k, v, causal=False))
        acc = np.zeros_like(want)
        n = 48
        for s in range(n):
            acc += np.asarray(flash_attention(
                q, k, v, causal=False, dropout_rate=0.25,
                dropout_rng=jax.random.key(100 + s)))
        err = np.abs(acc / n - want).max() / (np.abs(want).max() + 1e-9)
        assert err < 0.15, f"dropout mean deviates {err:.3f} from undropped"

    def test_backward_matches_finite_difference(self, monkeypatch):
        # The bwd kernels regenerate the same keep mask from the same seed:
        # the VJP must match a central finite difference of the (fixed-mask,
        # deterministic) forward.
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
        from distributed_tensorflow_tpu.ops import flash_attention

        q, k, v = make_qkv(B=1, T=128, H=1, D=16, seed=14)
        rng = jax.random.key(7)
        w = jnp.asarray(
            np.random.RandomState(5).randn(*q.shape).astype(np.float32))

        def f(q_, k_, v_):
            out = flash_attention(q_, k_, v_, causal=True, dropout_rate=0.2,
                                  dropout_rng=rng)
            return jnp.sum(out * w)

        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        rs = np.random.RandomState(6)
        for idx, (x, gx) in enumerate(zip((q, k, v), g)):
            d = jnp.asarray(rs.randn(*x.shape).astype(np.float32))
            eps = 1e-3
            args = [q, k, v]
            ap = list(args); ap[idx] = x + eps * d
            am = list(args); am[idx] = x - eps * d
            fd = (f(*ap) - f(*am)) / (2 * eps)
            an = jnp.sum(gx * d)
            np.testing.assert_allclose(
                float(fd), float(an), rtol=2e-2, atol=2e-2)

    def test_requires_rng(self):
        from distributed_tensorflow_tpu.ops import flash_attention

        q, k, v = make_qkv(seed=15)
        with pytest.raises(ValueError):
            flash_attention(q, k, v, dropout_rate=0.5)


class TestFlashOnMesh:
    """``flash_attention(mesh=...)``: under a mesh the kernel runs per
    (batch, head) shard inside a shard_map, because a Mosaic kernel cannot
    be handed to the automatic partitioner.  The interpreter stands in for
    the chip; tests/test_chip_compile.py asks the TPU compiler itself."""

    MESHES = [
        {"data": 8},
        {"data": 4, "tensor": 2},
        {"data": 2, "fsdp": 2, "tensor": 2},
        {"tensor": 2, "data": 1, "pipe": 4},  # idle axes stay out of the way
    ]

    @pytest.mark.parametrize("axes", MESHES,
                             ids=lambda a: "x".join(f"{k}{v}"
                                                    for k, v in a.items()))
    @pytest.mark.parametrize("masked", [False, True])
    def test_matches_dense_fwd_and_bwd(self, monkeypatch, devices8, axes,
                                       masked):
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh
        from distributed_tensorflow_tpu.ops import flash_attention
        from distributed_tensorflow_tpu.ops.flash_attention import _dense

        mesh = build_mesh(MeshConfig(**axes), devices8)
        q, k, v = make_qkv(B=8, T=128, H=4, D=16, seed=21)
        mask = None
        if masked:
            lengths = np.array([128, 100, 64, 1, 127, 33, 128, 90])
            mask = jnp.asarray(
                (np.arange(128)[None] < lengths[:, None]).astype(np.int32))
        w = jnp.asarray(
            np.random.RandomState(22).randn(*q.shape).astype(np.float32))
        scale = 1 / np.sqrt(q.shape[-1])

        def sharded(q_, k_, v_):
            return jnp.sum(w * flash_attention(
                q_, k_, v_, causal=not masked, kv_mask=mask, mesh=mesh))

        def dense(q_, k_, v_):
            return jnp.sum(w * _dense(
                q_, k_, v_, causal=not masked, scale=scale, kv_mask=mask))

        got, g_got = jax.jit(jax.value_and_grad(sharded, (0, 1, 2)))(q, k, v)
        want, g_want = jax.value_and_grad(dense, (0, 1, 2))(q, k, v)
        np.testing.assert_allclose(float(got), float(want), rtol=1e-4)
        for a, b in zip(g_got, g_want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)

    def test_takes_the_shard_map_only_when_the_kernel_runs(self, devices8):
        """Off-TPU without the interpreter the dense path is partitioned by
        GSPMD as before: no shard_map, no divisibility demand."""
        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh
        from distributed_tensorflow_tpu.ops import flash_attention

        mesh = build_mesh(MeshConfig(), devices8)
        q, k, v = make_qkv(B=2, T=128, H=2, D=16, seed=23)  # 2 % 8 != 0
        jaxpr = jax.make_jaxpr(
            lambda *a: flash_attention(*a, mesh=mesh))(q, k, v)
        assert "shard_map" not in str(jaxpr)

    def test_indivisible_batch_is_refused_with_the_reason(self, monkeypatch,
                                                          devices8):
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh
        from distributed_tensorflow_tpu.ops import flash_attention

        mesh = build_mesh(MeshConfig(), devices8)
        q, k, v = make_qkv(B=2, T=128, H=2, D=16, seed=24)
        with pytest.raises(ValueError, match="must divide over"):
            flash_attention(q, k, v, mesh=mesh)

    def test_tpu_refusal_is_logged_once_per_shape(self, monkeypatch, caplog):
        """A caller who asked for the kernel on a TPU never gets the dense
        path without a word: one WARNING per shape, with the reason."""
        import importlib
        import logging

        fa = importlib.import_module(
            "distributed_tensorflow_tpu.ops.flash_attention")
        monkeypatch.setattr(fa, "_platform", lambda: "tpu")
        monkeypatch.setattr(fa, "_WARNED_SHAPES", set())
        awkward = jax.ShapeDtypeStruct((2, 200, 4, 64), jnp.bfloat16)
        with caplog.at_level(logging.WARNING, logger=fa.__name__):
            assert not fa._supported(awkward, True)
            assert not fa._supported(awkward, True)
            assert fa._supported(
                jax.ShapeDtypeStruct((2, 256, 4, 64), jnp.bfloat16), True)
        hits = [r for r in caplog.records if "DENSE path" in r.getMessage()]
        assert len(hits) == 1 and "seq len 200" in hits[0].getMessage()
