"""Paged KV cache tests: the host-side block allocator, the engine's
paged cache APIs, and the load-bearing property of the whole design —
greedy decode through block tables is token-for-token identical to the
dense resident cache, on both acceptance meshes.

Parity is exact array equality (CPU greedy decode is deterministic, and
with ``kv_dtype=None``/``"bfloat16"`` the pool stores the same bits the
dense cache would).  ``kv_dtype="int8"`` is lossy by construction, so it
gets a logits-tolerance check at the model layer plus an end-to-end
completion check, not bitwise parity.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.models.gpt2 import GPT2, GPT2Config, PagedKVConfig
from distributed_tensorflow_tpu.serve import ContinuousScheduler, ServeEngine
from distributed_tensorflow_tpu.serve.paged import (
    TRASH_BLOCK,
    BlockAllocator,
    BlockExhaustedError,
)
from tests.helpers import fixed_reference


def _mixed_requests(vocab, n=20, seed=1):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        length = (4, 6, 9)[i % 3]
        horizon = (2, 5, 3, 7)[i % 4]
        reqs.append((rng.integers(0, vocab, size=(length,), dtype=np.int32),
                     horizon))
    return reqs


@pytest.fixture(scope="module")
def gpt2_engine(request):
    mesh_dp = request.getfixturevalue("mesh_dp")
    eng = ServeEngine("gpt2", mesh=mesh_dp, preset="tiny")
    yield eng
    eng.close()


# ---------------------------------------------------------------------------
# BlockAllocator: pure host-side unit tests
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def test_fresh_pool_allocates_low_ids_first(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        assert a.capacity == 7          # block 0 reserved
        assert a.allocate(3) == [1, 2, 3]
        assert a.free_count == 4 and a.used_count == 3

    def test_trash_block_never_handed_out(self):
        a = BlockAllocator(num_blocks=4, block_size=2)
        assert TRASH_BLOCK not in a.allocate(3)

    def test_exhaustion_raises(self):
        a = BlockAllocator(num_blocks=4, block_size=2)
        a.allocate(2)
        with pytest.raises(BlockExhaustedError, match="only 1/3 free"):
            a.allocate(2)
        # the failed call must not have consumed anything
        assert a.free_count == 1

    def test_free_and_lifo_reuse(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        first = a.allocate(3, slot=5)
        a.free(first)
        assert a.free_count == a.capacity
        # LIFO: the just-freed blocks come back first, in reverse order
        assert a.allocate(3) == first[::-1]

    def test_double_free_and_trash_free_rejected(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        blocks = a.allocate(2)
        a.free(blocks)
        with pytest.raises(ValueError, match="double free"):
            a.free([blocks[0]])
        with pytest.raises(ValueError, match="trash"):
            a.free([TRASH_BLOCK])

    def test_stats_and_high_water(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        held = a.allocate(5)
        a.free(held[2:])
        s = a.stats()
        assert s["blocks_total"] == 7.0
        assert s["blocks_in_use"] == 2.0
        assert s["blocks_free"] == 5.0
        assert s["blocks_high_water"] == 5.0  # peak, not current
        assert s["block_utilization"] == pytest.approx(2 / 7)

    def test_blocks_for_tokens(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        assert [a.blocks_for_tokens(t) for t in (0, 1, 4, 5, 8)] == \
            [0, 1, 1, 2, 2]

    def test_geometry_validation(self):
        with pytest.raises(ValueError, match="num_blocks"):
            BlockAllocator(num_blocks=1, block_size=4)
        with pytest.raises(ValueError, match="block_size"):
            BlockAllocator(num_blocks=4, block_size=0)


class TestPagedKVConfig:
    def test_validation(self):
        with pytest.raises(ValueError):
            PagedKVConfig(block_size=0)
        with pytest.raises(ValueError):
            PagedKVConfig(num_blocks=1)
        with pytest.raises(TypeError):
            PagedKVConfig(kv_dtype="not_a_dtype")

    def test_geometry_helpers(self):
        cfg = PagedKVConfig(block_size=8, num_blocks=16)
        assert cfg.usable_blocks == 15
        assert cfg.blocks_for(17) == 3
        assert cfg.max_blocks_per_slot(32) == 4

    def test_storage_dtype(self):
        assert PagedKVConfig().storage_dtype(jnp.bfloat16) == jnp.bfloat16
        assert (PagedKVConfig(kv_dtype="int8").storage_dtype(jnp.bfloat16)
                == jnp.int8)
        assert (PagedKVConfig(kv_dtype="float32").storage_dtype(jnp.bfloat16)
                == jnp.float32)
        assert PagedKVConfig(kv_dtype="int8").quantized


# ---------------------------------------------------------------------------
# Engine layer: paged cache init + call validation
# ---------------------------------------------------------------------------

class TestEnginePagedAPIs:
    def test_init_paged_cache_validates_geometry(self, gpt2_engine):
        pcfg = PagedKVConfig(block_size=8, num_blocks=64)
        with pytest.raises(ValueError, match="multiple"):
            gpt2_engine.init_paged_cache(3, 16, paged=pcfg)
        n_pos = gpt2_engine.module.cfg.n_positions
        with pytest.raises(ValueError, match="n_positions"):
            gpt2_engine.init_paged_cache(8, n_pos + 1, paged=pcfg)
        # a pool that cannot hold even ONE max-length request is an error
        with pytest.raises(ValueError, match="usable blocks"):
            gpt2_engine.init_paged_cache(
                8, 32, paged=PagedKVConfig(block_size=8, num_blocks=4))

    def test_paged_and_block_tables_go_together(self, gpt2_engine):
        pcfg = PagedKVConfig(block_size=8, num_blocks=33)
        cache = gpt2_engine.init_paged_cache(8, 32, paged=pcfg)
        prompt = np.zeros((1, 4), np.int32)
        with pytest.raises(ValueError, match="together"):
            gpt2_engine.prefill_into_slots(cache, prompt, [0], paged=pcfg)
        with pytest.raises(ValueError, match="together"):
            gpt2_engine.decode_slots(
                cache, np.zeros((8, 1), np.int32), np.ones((8,), bool),
                block_tables=np.zeros((8, 4), np.int32))

    def test_sized_down_pool_shrinks_kv_hbm(self, gpt2_engine):
        """The memory claim at the byte level: a pool at ~half the dense
        token capacity costs <= 0.5x the dense cache bytes; int8 storage
        roughly halves it again (scales cost a little back)."""
        dense = gpt2_engine.cache_hbm_bytes(
            gpt2_engine.init_slot_cache(8, 32))
        half_pool = PagedKVConfig(block_size=8, num_blocks=17)  # 16 usable
        paged = gpt2_engine.cache_hbm_bytes(
            gpt2_engine.init_paged_cache(8, 32, paged=half_pool))
        int8 = gpt2_engine.cache_hbm_bytes(gpt2_engine.init_paged_cache(
            8, 32, paged=PagedKVConfig(block_size=8, num_blocks=17,
                                       kv_dtype="int8")))
        assert paged <= 0.60 * dense  # 0.5x K/V + index/trash overhead
        assert int8 < 0.70 * paged


# ---------------------------------------------------------------------------
# Parity: paged == dense, token for token
# ---------------------------------------------------------------------------

class TestPagedParity:
    def test_mixed_traffic_parity_mesh_dp(self, gpt2_engine):
        """THE acceptance property on the data=8 mesh: greedy decode
        through block tables matches the fixed-batch reference token for
        token, with more requests than slots so blocks are freed and
        reused mid-run."""
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab, n=20)
        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=32,
                                 cache_mode="paged", block_size=8) as sched:
            futs = [sched.submit(p, max_new_tokens=m) for p, m in reqs]
            outs = [f.result(timeout=300) for f in futs]
            s = sched.stats()
            hist = sched.blocks_per_request_hist()
        for (prompt, horizon), out in zip(reqs, outs):
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, horizon))
        # every retired request returned its blocks
        assert s["blocks_in_use"] == 0.0
        assert s["blocks_high_water"] > 0.0
        assert sum(hist.values()) == len(reqs)
        assert s["blocks_per_request_max"] <= s["blocks_total"]

    def test_parity_under_tensor_parallel_mesh(self, mesh_2d):
        """Same parity on data=4 x tensor=2: pool heads shard over the
        tensor axis (gpt2_cache_rules), block tables stay host-side."""
        with ServeEngine("gpt2", mesh=mesh_2d, preset="tiny") as eng:
            vocab = eng.module.cfg.vocab_size
            reqs = _mixed_requests(vocab, n=10, seed=7)
            with ContinuousScheduler(eng, num_slots=4, max_total_len=32,
                                     cache_mode="paged",
                                     block_size=8) as sched:
                futs = [sched.submit(p, max_new_tokens=m) for p, m in reqs]
                outs = [f.result(timeout=300) for f in futs]
            for (prompt, horizon), out in zip(reqs, outs):
                np.testing.assert_array_equal(
                    out, fixed_reference(eng, prompt, horizon))

    def test_bfloat16_kv_dtype_is_exact(self, gpt2_engine):
        """kv_dtype naming the COMPUTE dtype is a plain cast-through —
        still bitwise, so still exact greedy parity."""
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab, n=8, seed=3)
        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=32,
                                 cache_mode="paged", block_size=8,
                                 kv_dtype="bfloat16") as sched:
            futs = [sched.submit(p, max_new_tokens=m) for p, m in reqs]
            outs = [f.result(timeout=300) for f in futs]
        for (prompt, horizon), out in zip(reqs, outs):
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, horizon))


class TestInt8KV:
    def test_int8_logits_close_to_dense(self):
        """Model-layer tolerance: a prefill through the int8 pool must
        reproduce the plain forward's logits within quantization error
        (per-token scales, 127 levels)."""
        cfg = GPT2Config.tiny(dtype=jnp.float32)
        model = GPT2(cfg)
        tokens = np.asarray(jax.random.randint(
            jax.random.key(1), (2, 6), 0, cfg.vocab_size))
        params = model.init(jax.random.key(0), tokens)["params"]
        full = model.apply({"params": params}, jnp.asarray(tokens))

        pcfg = PagedKVConfig(block_size=4, num_blocks=9, kv_dtype="int8")
        bt = np.zeros((4, 2), np.int32)
        bt[3] = [1, 2]
        bt[0] = [3, 4]
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((4, 6), jnp.int32), decode=True,
            slot_ids=jnp.arange(4), paged=pcfg,
            block_tables=jnp.asarray(bt)))["cache"]
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        logits, _ = model.apply(
            {"params": params, "cache": cache}, jnp.asarray(tokens),
            decode=True, slot_ids=jnp.asarray([3, 0]), paged=pcfg,
            block_tables=jnp.asarray(bt), mutable=["cache"])
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                                   rtol=0.0, atol=0.05)

    @pytest.mark.serve_slow
    def test_int8_end_to_end_completes(self, gpt2_engine):
        """End-to-end int8 serving: all futures resolve with valid tokens
        of the right shape (bitwise parity is not promised here)."""
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab, n=10, seed=5)
        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=32,
                                 cache_mode="paged", block_size=8,
                                 kv_dtype="int8") as sched:
            futs = [sched.submit(p, max_new_tokens=m) for p, m in reqs]
            outs = [f.result(timeout=300) for f in futs]
            s = sched.stats()
        assert s["completed"] == float(len(reqs))
        for (_, horizon), out in zip(reqs, outs):
            assert out.shape == (horizon,)
            assert (out >= 0).all() and (out < vocab).all()


# ---------------------------------------------------------------------------
# The pool's layout: lane-dense blocks, carried through the layer loop
# ---------------------------------------------------------------------------

def _unstacked(params, n_layer):
    """Scanned ``blocks`` parameters as the unrolled ``h_i`` layout."""
    out = {k: v for k, v in params.items() if k != "blocks"}
    for i in range(n_layer):
        out[f"h_{i}"] = jax.tree.map(lambda p: p[i], params["blocks"])
    return out


def _cache_shapes(model, num_slots, total_len, **kwargs):
    return jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((num_slots, total_len), jnp.int32),
        decode=True, slot_ids=jnp.arange(num_slots), **kwargs))["cache"]


def _empty_cache(model, num_slots, total_len, **kwargs):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        _cache_shapes(model, num_slots, total_len, **kwargs))


class TestPoolLayout:
    NUM_SLOTS, TOTAL, BLOCK = 4, 16, 4

    def _tables(self):
        # slots 3 and 0 are served; their blocks interleave in the pool.
        bt = np.zeros((self.NUM_SLOTS, self.TOTAL // self.BLOCK), np.int32)
        bt[3] = [2, 5, 7, 1]
        bt[0] = [4, 3, 8, 6]
        return jnp.asarray(bt)

    @pytest.mark.parametrize("scan_layers", [True, False],
                             ids=["scan", "unrolled"])
    def test_pool_leaves_are_lane_dense(self, scan_layers):
        cfg = GPT2Config.tiny(scan_layers=scan_layers)
        pcfg = PagedKVConfig(block_size=self.BLOCK, num_blocks=9,
                             kv_dtype="int8")
        cache = _cache_shapes(GPT2(cfg), self.NUM_SLOTS, self.TOTAL,
                              paged=pcfg, block_tables=self._tables())
        lead = (cfg.n_layer,) if scan_layers else ()
        layer = cache["blocks"] if scan_layers else cache["h_0"]
        for name in ("cached_key_pool", "cached_value_pool"):
            assert layer[name].shape == lead + (9, self.BLOCK, cfg.d_model)
            assert layer[name].dtype == jnp.int8
        for name in ("key_scale", "value_scale"):
            assert layer[name].shape == lead + (9, self.BLOCK)
        assert layer["cache_index"].shape == lead + (self.NUM_SLOTS,)

    @pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
    @pytest.mark.parametrize("scan_layers", [True, False],
                             ids=["scan", "unrolled"])
    def test_paged_follows_dense_slot_cache(self, scan_layers, kv_dtype):
        """A prefill of two slots and four decode steps, the paged pool
        against the dense slot cache on the same tokens: the same logits
        and the same greedy token at every position (int8 within its
        quantisation error), with the layer loop scanned (pool carried)
        and unrolled (a pool a layer)."""
        cfg = GPT2Config.tiny(scan_layers=scan_layers)
        model = GPT2(cfg)
        stacked = GPT2(GPT2Config.tiny()).init(
            jax.random.key(0), jnp.zeros((2, 6), jnp.int32))["params"]
        params = stacked if scan_layers else _unstacked(stacked, cfg.n_layer)
        pcfg = PagedKVConfig(block_size=self.BLOCK, num_blocks=9,
                             kv_dtype=kv_dtype)
        tables = self._tables()
        paged = {"paged": pcfg, "block_tables": tables}
        dense_cache = _empty_cache(model, self.NUM_SLOTS, self.TOTAL)
        paged_cache = _empty_cache(model, self.NUM_SLOTS, self.TOTAL, **paged)

        slots = jnp.asarray([3, 0])

        def stepper(**kwargs):
            # One program a shape of call (the prefill's, a step's), not an
            # op at a time with the layer scan compiled anew at every call.
            apply = jax.jit(lambda cache, tokens: model.apply(
                {"params": params, "cache": cache}, tokens, decode=True,
                slot_ids=slots, mutable=["cache"], **kwargs))

            def step(cache, tokens):
                logits, mutated = apply(cache, tokens)
                return np.asarray(logits[:, -1], np.float32), mutated["cache"]
            return step

        dense_step, paged_step = stepper(), stepper(**paged)
        tokens = jax.random.randint(jax.random.key(1), (2, 6), 0,
                                    cfg.vocab_size)
        atol = 0.05 if kv_dtype == "int8" else 0.0
        for _ in range(5):
            want, dense_cache = dense_step(dense_cache, tokens)
            got, paged_cache = paged_step(paged_cache, tokens)
            np.testing.assert_allclose(got, want, rtol=0.0, atol=atol)
            if kv_dtype is None:
                np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))
            tokens = jnp.asarray(want.argmax(-1))[:, None]   # teacher-forced
        index = (paged_cache["blocks"]["cache_index"][0] if scan_layers
                 else paged_cache["h_0"]["cache_index"])
        np.testing.assert_array_equal(index, [10, 0, 0, 10])

    @pytest.mark.parametrize("scan_layers", [True, False],
                             ids=["scan", "unrolled"])
    def test_scheduler_parity_scan_layers(self, mesh_dp, scan_layers):
        """End to end through the scheduler and a fused decode: greedy
        streams through the block tables match the fixed-batch dense
        reference token for token with the layer stack scanned and
        unrolled."""
        with ServeEngine("gpt2", mesh=mesh_dp,
                         config=GPT2Config.tiny(
                             scan_layers=scan_layers)) as eng:
            reqs = _mixed_requests(eng.module.cfg.vocab_size, n=10, seed=13)
            with ContinuousScheduler(eng, num_slots=8, max_total_len=32,
                                     cache_mode="paged", block_size=8,
                                     megastep=3) as sched:
                futs = [sched.submit(p, max_new_tokens=m) for p, m in reqs]
                outs = [f.result(timeout=300) for f in futs]
            for (prompt, horizon), out in zip(reqs, outs):
                np.testing.assert_array_equal(
                    out, fixed_reference(eng, prompt, horizon))

    @pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
    def test_block_swap_round_trip_is_byte_exact(self, gpt2_engine, kv_dtype):
        """KV tiering's unit: one block gathered to the host and scattered
        into another block arrives byte for byte (scales with it under
        int8), and no other block changes."""
        pcfg = PagedKVConfig(block_size=8, num_blocks=33, kv_dtype=kv_dtype)
        cache = gpt2_engine.init_paged_cache(8, 32, paged=pcfg)
        tables = np.zeros((8, 4), np.int32)
        tables[2] = [5, 6, 0, 0]
        prompt = np.arange(1, 13, dtype=np.int32)[None, :]
        _, cache = gpt2_engine.prefill_into_slots(
            cache, prompt, [2], paged=pcfg, block_tables=tables)
        cfg = gpt2_engine.module.cfg
        payload = gpt2_engine.gather_kv_block(cache, 5, paged=pcfg)
        pools = [p for p in payload if p.ndim == 3]
        assert len(payload) == (4 if kv_dtype == "int8" else 2)
        assert [p.shape for p in pools] == [(cfg.n_layer, 8, cfg.d_model)] * 2
        assert all(np.any(p != 0) for p in payload)
        before = jax.device_get(cache)
        cache = gpt2_engine.scatter_kv_block(cache, 9, payload, paged=pcfg)
        for got, want in zip(
                gpt2_engine.gather_kv_block(cache, 9, paged=pcfg), payload):
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(got, want)
        after = jax.device_get(cache)
        for name, leaf in after["blocks"].items():
            old = before["blocks"][name]
            if name == "cache_index":
                np.testing.assert_array_equal(leaf, old)
                continue
            others = np.delete(np.arange(33), 9)
            np.testing.assert_array_equal(leaf[:, others], old[:, others])

    @pytest.mark.parametrize("per_shard", [False, True],
                             ids=["tensor2", "tensor2-data_shards2"])
    @pytest.mark.parametrize("scan_layers", [True, False],
                             ids=["scan", "unrolled"])
    def test_cache_rules_shard_the_same_bytes(self, devices8, scan_layers,
                                              per_shard):
        """On data=2 x tensor=2 every chip holds, of each pool, the heads
        the (.., heads, head_dim) layout gave it (now a contiguous range of
        columns), and under per-shard pools its data shard's blocks; the
        scale tables follow the blocks."""
        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh
        from distributed_tensorflow_tpu.models.gpt2 import gpt2_cache_rules

        mesh = build_mesh(MeshConfig(data=2, tensor=2), devices8[:4])
        cfg = GPT2Config.tiny(scan_layers=scan_layers)
        pcfg = PagedKVConfig(block_size=4, num_blocks=10, kv_dtype="int8",
                             data_shards=2 if per_shard else 1)
        shapes = _cache_shapes(GPT2(cfg), 4, 16, paged=pcfg,
                               block_tables=jnp.zeros((4, 4), jnp.int32))
        shardings = gpt2_cache_rules(per_shard_pools=per_shard).shardings_for(
            mesh, shapes)
        layer = "blocks" if scan_layers else "h_0"
        head_dim = cfg.d_model // cfg.n_head
        whole = slice(None)
        for data in range(2):
            for tensor in range(2):
                device = mesh.devices[tuple(
                    {"data": data, "tensor": tensor}.get(axis, 0)
                    for axis in mesh.axis_names)]
                blocks = (slice(5 * data, 5 * data + 5) if per_shard
                          else whole)
                heads = slice(tensor * cfg.n_head // 2,
                              (tensor + 1) * cfg.n_head // 2)
                columns = slice(heads.start * head_dim, heads.stop * head_dim)
                for name in ("cached_key_pool", "cached_value_pool"):
                    leaf, sh = shapes[layer][name], shardings[layer][name]
                    index = sh.devices_indices_map(leaf.shape)[device]
                    assert _slices(index[-3:], leaf.shape[-3:]) == _slices(
                        (blocks, whole, columns), leaf.shape[-3:])
                    if scan_layers:
                        assert _slices(index[:1], leaf.shape[:1]) == _slices(
                            (whole,), leaf.shape[:1])
                for name in ("key_scale", "value_scale"):
                    leaf, sh = shapes[layer][name], shardings[layer][name]
                    index = sh.devices_indices_map(leaf.shape)[device]
                    assert _slices(index[-2:], leaf.shape[-2:]) == _slices(
                        (blocks, whole), leaf.shape[-2:])


def _slices(index, shape):
    return [s.indices(n) for s, n in zip(index, shape)]


# ---------------------------------------------------------------------------
# Backpressure + admission-time rejection
# ---------------------------------------------------------------------------

class TestBlockBackpressure:
    def test_exhausted_pool_defers_admission_not_correctness(self,
                                                             gpt2_engine):
        """A pool that fits only ONE request's worst case serializes
        admission (later requests wait for retirement's bulk-free) but
        every stream still matches the reference — backpressure, not
        corruption."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(11)
        reqs = [(rng.integers(0, vocab, size=(6,), dtype=np.int32), 6)
                for _ in range(3)]
        # worst case per request: blocks_for(6 + 6 - 1) = 3 of size 4;
        # 5 usable blocks -> the second request cannot co-reside.
        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=16,
                                 cache_mode="paged", block_size=4,
                                 num_blocks=6) as sched:
            futs = [sched.submit(p, max_new_tokens=m) for p, m in reqs]
            outs = [f.result(timeout=300) for f in futs]
            s = sched.stats()
        assert s["blocks_high_water"] <= 5.0
        assert s["completed"] == 3.0
        for (prompt, horizon), out in zip(reqs, outs):
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, horizon))

    def test_pool_too_small_for_one_request_rejected_at_init(self,
                                                             gpt2_engine):
        """A pool that cannot hold even one max-length request is a
        config error at CONSTRUCTION — nothing could ever decode, so it
        must not wait for a submit to fail."""
        with pytest.raises(ValueError, match="usable blocks"):
            ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=32,
                                cache_mode="paged", block_size=4,
                                num_blocks=4, start=False)

    def test_submit_rejects_empty_prompt(self, gpt2_engine):
        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=16,
                                 start=False) as sched:
            with pytest.raises(ValueError, match="at least one token"):
                sched.submit(np.zeros((0,), np.int32), max_new_tokens=4)

    def test_submit_rejects_overlong_request_in_both_modes(self,
                                                           gpt2_engine):
        for kw in ({}, {"cache_mode": "paged", "block_size": 4}):
            with ContinuousScheduler(gpt2_engine, num_slots=8,
                                     max_total_len=16, start=False,
                                     **kw) as sched:
                with pytest.raises(ValueError, match="max_total_len"):
                    sched.submit(np.zeros((12,), np.int32),
                                 max_new_tokens=8)

    def test_scheduler_config_validation(self, gpt2_engine):
        with pytest.raises(ValueError, match="cache_mode"):
            ContinuousScheduler(gpt2_engine, cache_mode="virtual",
                                start=False)
        with pytest.raises(ValueError, match="paged"):
            ContinuousScheduler(gpt2_engine, cache_mode="dense",
                                kv_dtype="int8", start=False)


# ---------------------------------------------------------------------------
# Block gauges on the stats / monitor surface
# ---------------------------------------------------------------------------

class TestBlockGauges:
    def test_dense_reports_trivially_full_pool(self, gpt2_engine):
        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=32,
                                 block_size=8) as sched:
            out = sched.submit(np.arange(4, dtype=np.int32),
                               max_new_tokens=2).result(timeout=300)
            s = sched.stats()
            hist = sched.blocks_per_request_hist()
        assert len(out) == 2
        per_slot = 32 // 8
        assert s["blocks_total"] == float(8 * per_slot)
        assert s["blocks_in_use"] == s["blocks_total"]
        assert s["blocks_free"] == 0.0
        assert s["block_utilization"] == 1.0
        # dense: every request pins a full slot row for its lifetime
        assert hist == {per_slot: 1}
        assert s["kv_hbm_bytes"] > 0.0

    def test_monitor_logs_block_line(self, gpt2_engine, caplog):
        from distributed_tensorflow_tpu.obs import ServeMonitorHook

        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=32,
                                 cache_mode="paged", block_size=8) as sched:
            hook = ServeMonitorHook(sched, every_steps=1)
            sched.submit(np.arange(5, dtype=np.int32),
                         max_new_tokens=3).result(timeout=300)
            m = hook.metrics()
            with caplog.at_level(
                    logging.INFO,
                    logger="distributed_tensorflow_tpu.obs.serve"):
                hook.log(1)
        for key in ("serve_blocks_total", "serve_blocks_free",
                    "serve_block_utilization", "serve_blocks_high_water",
                    "serve_blocks_per_request_mean", "serve_kv_hbm_bytes"):
            assert key in m, m
        assert any("kv blocks=" in r.message and "util=" in r.message
                   for r in caplog.records)
