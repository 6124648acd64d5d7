"""Ring attention correctness: the sharded ring program must equal dense
softmax attention (it is exact attention, not an approximation).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh
from distributed_tensorflow_tpu.parallel.ring_attention import (
    _dense_attention,
    ring_attention,
)


@pytest.fixture(scope="module")
def mesh_ctx():
    import jax

    return build_mesh(MeshConfig(data=1, context=8), jax.devices())


def make_qkv(B=2, T=32, H=4, D=8, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    return mk(), mk(), mk()


@functools.partial(jax.jit, static_argnames=(
    "mesh", "causal", "rate", "chunk_size", "use_flash"))
def ring(q, k, v, rng=None, kv_mask=None, *, mesh, causal=True, rate=0.0,
         chunk_size=None, use_flash=None):
    """``ring_attention`` as one program of its arrays: the dropout key is
    an argument, so calls that differ only in the key share one compile."""
    return ring_attention(q, k, v, mesh=mesh, causal=causal,
                          chunk_size=chunk_size, kv_mask=kv_mask,
                          use_flash=use_flash, dropout_rate=rate,
                          dropout_rng=rng)


def sharded(mesh, *arrays):
    sh = NamedSharding(mesh, P(None, "context"))
    return [jax.device_put(x, sh) for x in arrays]


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, mesh_ctx, causal):
        q, k, v = make_qkv()
        qs, ks, vs = sharded(mesh_ctx, q, k, v)
        got = ring(qs, ks, vs, mesh=mesh_ctx, causal=causal)
        want = _dense_attention(q, k, v, causal=causal,
                                scale=1.0 / np.sqrt(q.shape[-1]))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_output_stays_sequence_sharded(self, mesh_ctx):
        q, k, v = make_qkv()
        qs, ks, vs = sharded(mesh_ctx, q, k, v)
        out = ring(qs, ks, vs, mesh=mesh_ctx)
        assert not out.sharding.is_fully_replicated

    def test_gradients_match_dense(self, mesh_ctx):
        q, k, v = make_qkv(T=16)
        qs, ks, vs = sharded(mesh_ctx, q, k, v)

        def loss_ring(q, k, v):
            return jnp.sum(ring(q, k, v, mesh=mesh_ctx) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(_dense_attention(
                q, k, v, causal=True, scale=1.0 / np.sqrt(q.shape[-1])) ** 2)

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(qs, ks, vs)
        g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for gr, gd in zip(g_ring, g_dense):
            np.testing.assert_allclose(
                np.asarray(gr), np.asarray(gd), rtol=1e-4, atol=1e-4
            )

    @pytest.mark.parametrize("causal", [False, True])
    def test_chunked_blocks_match_dense(self, mesh_ctx, causal):
        """chunk_size < per-shard block length: the kv block is consumed
        in chunks under a scan (bounded score tile) — result unchanged."""
        q, k, v = make_qkv(seed=11)
        qs, ks, vs = sharded(mesh_ctx, q, k, v)
        got = ring(qs, ks, vs, mesh=mesh_ctx, causal=causal,
                   chunk_size=2)  # per-shard block is 4
        want = _dense_attention(q, k, v, causal=causal,
                                scale=1.0 / np.sqrt(q.shape[-1]))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )

    def test_chunked_gradients_match_dense(self, mesh_ctx):
        q, k, v = make_qkv(T=16, seed=13)
        qs, ks, vs = sharded(mesh_ctx, q, k, v)

        def loss_ring(q, k, v):
            return jnp.sum(ring(q, k, v, mesh=mesh_ctx, chunk_size=1) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(_dense_attention(
                q, k, v, causal=True, scale=1.0 / np.sqrt(q.shape[-1])) ** 2)

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(qs, ks, vs)
        g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for gr, gd in zip(g_ring, g_dense):
            np.testing.assert_allclose(
                np.asarray(gr), np.asarray(gd), rtol=1e-4, atol=1e-4
            )

    @pytest.mark.parametrize("causal", [False, True])
    def test_kv_mask_matches_dense(self, mesh_ctx, causal):
        """Key padding mask rotates around the ring with K/V; result equals
        masked dense attention (fwd + grads) — einsum block path."""
        q, k, v = make_qkv(seed=17)
        T = q.shape[1]
        lens = np.array([T - 5, T // 2])
        mask = jnp.asarray(
            (np.arange(T)[None, :] < lens[:, None]).astype(np.int32))
        qs, ks, vs = sharded(mesh_ctx, q, k, v)
        ms, = sharded(mesh_ctx, mask)
        scale = 1.0 / np.sqrt(q.shape[-1])

        got = ring(qs, ks, vs, kv_mask=ms, mesh=mesh_ctx, causal=causal)
        want = _dense_attention(q, k, v, causal=causal, scale=scale,
                                kv_mask=mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

        g_ring = jax.jit(jax.grad(lambda a, b, c: jnp.sum(ring(
            a, b, c, kv_mask=ms, mesh=mesh_ctx, causal=causal) ** 2),
            argnums=(0, 1, 2)))(qs, ks, vs)
        g_dense = jax.grad(lambda a, b, c: jnp.sum(_dense_attention(
            a, b, c, causal=causal, scale=scale, kv_mask=mask) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for gr, gd in zip(g_ring, g_dense):
            np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("masked", [False, True])
    def test_flash_blocks_match_dense(self, mesh_ctx, monkeypatch, causal,
                                      masked):
        """VERDICT r2 #2 done-criterion: the ring consuming the Pallas
        flash kernel per block (interpreter on CPU) equals dense attention
        in fwd AND grads.  Per-shard length 128 = one whole kernel block;
        causal dispatch (diag/below/skip) and the lse combine are what's
        under test."""
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
        B, T, H, D = 2, 8 * 128, 2, 16
        rng = np.random.RandomState(29)
        q, k, v = (jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
                   for _ in range(3))
        mask = None
        mask_dev = None
        if masked:
            lens = np.array([900, 640])
            mask = jnp.asarray(
                (np.arange(T)[None, :] < lens[:, None]).astype(np.int32))
            mask_dev, = sharded(mesh_ctx, mask)
        qs, ks, vs = sharded(mesh_ctx, q, k, v)
        scale = 1.0 / np.sqrt(D)

        got = ring(qs, ks, vs, kv_mask=mask_dev, mesh=mesh_ctx,
                   causal=causal, use_flash=True)
        want = _dense_attention(q, k, v, causal=causal, scale=scale,
                                kv_mask=mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

        w = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
        g_ring = jax.jit(jax.grad(lambda a, b, c: jnp.sum(ring(
            a, b, c, kv_mask=mask_dev, mesh=mesh_ctx, causal=causal,
            use_flash=True) * w), argnums=(0, 1, 2)))(qs, ks, vs)
        g_dense = jax.grad(lambda a, b, c: jnp.sum(_dense_attention(
            a, b, c, causal=causal, scale=scale, kv_mask=mask) * w),
            argnums=(0, 1, 2))(q, k, v)
        for gr, gd in zip(g_ring, g_dense):
            np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                       rtol=1e-4, atol=1e-4)

    def test_single_device_axis_falls_back(self, mesh_dp):
        # mesh without a context axis (size 1) → dense path
        q, k, v = make_qkv(T=8)
        out = ring_attention(q, k, v, mesh=mesh_dp, causal=True)
        want = _dense_attention(q, k, v, causal=True,
                                scale=1.0 / np.sqrt(q.shape[-1]))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-6)


class TestRingDropout:
    """Attention-prob dropout under the ring (einsum block engine on CPU):
    per-block dropout with undropped softmax statistics composes EXACTLY
    under the lse combine, so the ring path no longer changes the recipe."""

    def _ring(self, mesh_ctx, q, k, v, rate, rng, causal=True):
        return np.asarray(ring(*sharded(mesh_ctx, q, k, v), rng,
                               mesh=mesh_ctx, causal=causal, rate=rate))

    def test_rate_zero_matches_dense_exactly(self, mesh_ctx):
        q, k, v = make_qkv(seed=21)
        got = self._ring(mesh_ctx, q, k, v, 0.0, None)
        want = _dense_attention(q, k, v, causal=True,
                                scale=1.0 / np.sqrt(q.shape[-1]))
        np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5,
                                   atol=2e-5)

    def test_deterministic_per_key_varies_across_keys(self, mesh_ctx):
        q, k, v = make_qkv(seed=22)
        a = self._ring(mesh_ctx, q, k, v, 0.3, jax.random.key(5))
        b = self._ring(mesh_ctx, q, k, v, 0.3, jax.random.key(5))
        c = self._ring(mesh_ctx, q, k, v, 0.3, jax.random.key(6))
        base = self._ring(mesh_ctx, q, k, v, 0.0, None)
        np.testing.assert_array_equal(a, b)
        assert not np.allclose(a, c)
        assert not np.allclose(a, base)

    def test_dropout_is_unbiased_vs_undropped(self, mesh_ctx):
        q, k, v = make_qkv(B=1, T=32, H=2, D=8, seed=23)
        base = self._ring(mesh_ctx, q, k, v, 0.0, None, causal=False)
        acc = np.zeros_like(base)
        n = 48
        for s in range(n):
            acc += self._ring(mesh_ctx, q, k, v, 0.25,
                              jax.random.key(200 + s), causal=False)
        err = np.abs(acc / n - base).max() / (np.abs(base).max() + 1e-9)
        assert err < 0.2, f"ring dropout mean deviates {err:.3f}"

    def test_chunked_blocks_support_dropout(self, mesh_ctx):
        q, k, v = make_qkv(seed=24)
        qs, ks, vs = sharded(mesh_ctx, q, k, v)
        out = np.asarray(ring(qs, ks, vs, jax.random.key(7), mesh=mesh_ctx,
                              chunk_size=2, rate=0.2))
        assert np.isfinite(out).all()
        base = self._ring(mesh_ctx, q, k, v, 0.0, None)
        assert not np.allclose(out, base)

    def test_gradients_flow_through_dropout(self, mesh_ctx):
        q, k, v = make_qkv(B=1, T=16, H=2, D=8, seed=25)
        qs, ks, vs = sharded(mesh_ctx, q, k, v)
        rng = jax.random.key(9)

        def loss(q_, k_, v_):
            out = ring(q_, k_, v_, rng, mesh=mesh_ctx, rate=0.2)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(qs, ks, vs)
        for arr in g:
            a = np.asarray(arr)
            assert np.isfinite(a).all()
            assert np.abs(a).max() > 0
