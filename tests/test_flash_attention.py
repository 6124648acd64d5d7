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


class TestCausalTriangle:
    """The resident kernels' split loop: tiles under the diagonal take no
    mask, the diagonal's tile is walked in pieces (128 wide; dQ's 256).  At
    T = 512 blocks of 256 give the forward and dK/dV one unmasked tile and
    two walked in two pieces (dQ keeps the positional mask: its piece is
    the block); one block of 512 is walked whole by all three, in four
    pieces and in dQ's two."""

    T = 512

    @pytest.fixture(params=[256, 512], ids=["blocks256", "one-block"])
    def fa(self, monkeypatch, request):
        import importlib

        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
        fa = importlib.import_module(
            "distributed_tensorflow_tpu.ops.flash_attention")
        block = request.param
        monkeypatch.setattr(fa, "BLOCK_Q", block)
        monkeypatch.setattr(fa, "BLOCK_K", block)
        sched = fa.schedule(self.T, block, block, True)
        dq = fa.schedule(self.T, block, block, True, fa.DQ_PIECE)
        assert (sched.piece, sched.unmasked_tiles, sched.diagonal_pieces,
                dq.piece, dq.masked_tiles, dq.diagonal_pieces) == (
            (128, 1, 4, 0, 3, 0) if block == 256 else (128, 0, 4, 256, 0, 2))
        return fa

    @pytest.mark.parametrize("with_lse", [False, True],
                             ids=["out", "out+lse"])
    @pytest.mark.parametrize("masked", [False, True],
                             ids=["nomask", "kvmask"])
    @pytest.mark.parametrize("scale", [0.25, 0.3], ids=["pow2", "inexact"])
    def test_split_loop_matches_dense_fwd_and_bwd(self, fa, masked, with_lse,
                                                  scale):
        """Forward, dQ, dK and dV against dense, with a key mask that leaves
        one row of the batch no valid key at all (the -inf guards' case),
        with the log-sum-exp's cotangent present (ring attention's form),
        and with a scale that folds into q and k exactly and one that does
        not."""
        q, k, v = make_qkv(B=2, T=self.T, H=2, D=16, seed=31)
        rng = np.random.RandomState(37)
        wo = jnp.asarray(rng.randn(*q.shape).astype(np.float32))
        wl = jnp.asarray(rng.randn(2, 2, self.T).astype(np.float32))
        mask = None
        if masked:
            lens = np.array([300, 0])    # crosses a piece; wholly masked
            mask = jnp.asarray(
                (np.arange(self.T)[None] < lens[:, None]).astype(np.int32))

        def loss(fn):
            def f(q_, k_, v_):
                o, lse = fn(q_, k_, v_)
                # a row with no valid key has lse = -1e30 by contract
                live = jnp.where(lse > -1e29, lse, 0.0)
                return jnp.sum(o * wo) + jnp.sum(live * wl)
            return f

        if with_lse:
            got_fn = lambda *a: fa.flash_attention_with_lse(
                *a, causal=True, kv_mask=mask, scale=scale)
        else:
            got_fn = lambda *a: (fa.flash_attention(
                *a, causal=True, kv_mask=mask, scale=scale),
                jnp.zeros((2, 2, self.T)))
        want_fn = lambda *a: fa._dense_with_lse(
            *a, causal=True, kv_mask=mask, scale=scale)
        if not with_lse:
            dense = want_fn
            want_fn = lambda *a: (dense(*a)[0], jnp.zeros((2, 2, self.T)))

        got_o, got_l = got_fn(q, k, v)
        want_o, want_l = want_fn(q, k, v)
        np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                                   rtol=2e-5, atol=2e-5)
        if with_lse and not masked:
            np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                                       rtol=2e-5, atol=2e-5)
        got = jax.grad(loss(got_fn), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(want_fn), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip(("dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                err_msg=f"{name} mismatch")

    def test_dropout_mask_agrees_between_forward_and_backward(self, fa,
                                                              monkeypatch):
        """The three kernels regenerate a tile's keep mask from (b, q block,
        k block) and take their pieces of it, the dK/dV kernel transposed.
        The TPU PRNG has no interpreter lowering, so a keep mask that is a
        plain function of (b, query, key) stands in for it: whichever
        kernel took a wrong piece would disagree with the dense form under
        the same mask."""
        rate, B, H, T = 0.25, 1, 2, self.T

        def keep_of(b, row, col):
            return (row * 7919 + col * 104729 + b * 1000003) % 97 >= 24

        def tile_dropout(seed_ref, b, qi, kj, shape, rate_):
            row = qi * shape[0] + jax.lax.broadcasted_iota(
                jnp.int32, shape, 0)
            col = kj * shape[1] + jax.lax.broadcasted_iota(
                jnp.int32, shape, 1)
            return jnp.where(keep_of(b + seed_ref[0], row, col),
                             1.0 / (1.0 - rate_), 0.0)

        monkeypatch.setattr(fa, "_tile_dropout", tile_dropout)
        q, k, v = make_qkv(B=B, T=T, H=H, D=16, seed=41)
        g = jnp.asarray(
            np.random.RandomState(43).randn(*q.shape).astype(np.float32))
        scale = 0.25
        seed = jnp.zeros((1,), jnp.int32)
        out, lse = fa._flash_fwd_tpu(
            q, k, v, None, causal=True, scale=scale, save_lse=True,
            dropout_rate=rate, seed=seed)
        got = (out,) + fa._flash_bwd_tpu(
            q, k, v, out, lse, g, None, None, causal=True, scale=scale,
            dropout_rate=rate, seed=seed)

        heads = np.arange(B * H).reshape(B, H, 1, 1)
        keep = keep_of(heads, np.arange(T).reshape(1, 1, T, 1),
                       np.arange(T).reshape(1, 1, 1, T))
        drop = jnp.asarray(keep / (1.0 - rate), jnp.float32)

        def dense(q_, k_, v_):
            s = jnp.einsum("bqhd,bkhd->bhqk", q_, k_) * scale
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
            return jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(s, axis=-1) * drop, v_)

        want_out, vjp = jax.vjp(dense, q, k, v)
        for name, a, b in zip(("out", "dq", "dk", "dv"), got,
                              (want_out,) + vjp(g)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                err_msg=f"{name} mismatch under dropout")

    @pytest.mark.parametrize("args,want", [
        # cell 1 and cell 4: one tile without a mask, two walked in four
        ((1024, 512, 512, True), (128, 1, 0, 8, 0.5625)),
        ((1024, 256, 256, True), (128, 6, 0, 8, 0.5625)),
        ((512, 256, 256, True), (128, 1, 0, 4, 0.625)),
        # one block, one piece: today's single body under the mask
        ((128, 128, 128, True), (0, 0, 1, 0, 1.0)),
        ((384, 128, 128, True), (0, 0, 6, 0, 6 / 9)),
        # blocks that differ are not walked
        ((1024, 512, 256, True), (0, 0, 6, 0, 0.75)),
        ((1024, 512, 512, False), (0, 4, 0, 0, 1.0)),
        ((512, 512, 512, False), (0, 1, 0, 0, 1.0)),
        # the cells since PR 43: one block, the triangle walked whole;
        # dQ's pieces are 256 wide
        ((1024, 1024, 1024, True), (128, 0, 0, 8, 0.5625)),
        ((1024, 1024, 1024, True, 256), (256, 0, 0, 4, 0.625)),
        ((512, 256, 256, True, 256), (0, 0, 3, 0, 0.75)),
    ], ids=["t1024-b512", "t1024-b256", "t512-b256", "t128", "t384-b128",
            "t1024-512x256", "noncausal", "noncausal-one-block",
            "t1024-one-block", "t1024-one-block-dq", "t512-b256-dq"])
    def test_schedule(self, args, want):
        """The one function the kernels' loops and this test both read."""
        import importlib

        fa = importlib.import_module(
            "distributed_tensorflow_tpu.ops.flash_attention")
        sched = fa.schedule(*args)
        assert (sched.block_q, sched.block_k) == args[1:3]
        assert sched[2:6] == want[:4]
        assert sched.share == pytest.approx(want[4])

    def test_schedule_is_logged_once_per_shape(self, fa, caplog,
                                               monkeypatch):
        import logging

        monkeypatch.setattr(fa, "_LOGGED_SCHEDULES", set())
        q, k, v = make_qkv(B=1, T=self.T, H=1, D=16, seed=47)
        loss = lambda q_: jnp.sum(fa.flash_attention(q_, k, v, causal=True))
        with caplog.at_level(logging.INFO, logger=fa.__name__):
            jax.grad(loss)(q)
            jax.grad(loss)(q)
        hits = sorted(r.getMessage() for r in caplog.records
                      if "of T^2 computed" in r.getMessage())
        assert len(hits) == 2       # the dQ kernel's, then the other two's
        if fa.BLOCK_Q == 256:
            assert "dQ" in hits[0] and "3 under the positional mask" \
                in hits[0] and "0.7500" in hits[0]
            assert "1 unmasked" in hits[1] and "4 diagonal pieces of 128" \
                in hits[1] and "0.6250" in hits[1]
        else:
            assert "dQ" in hits[0] and "2 diagonal pieces of 256" in hits[0]
            assert "4 diagonal pieces of 128" in hits[1]
