"""Prefix caching tests: chained content keys, the refcounted allocator
(sharing, eviction, invalidation, the double-free guard), and the parity
oracle — greedy streams with ``prefix_cache`` on are bit-identical to the
uncached paged path on both acceptance meshes, including copy-on-write
divergence and eviction under pool pressure.

Parity is exact array equality: a cache hit maps the very blocks an
uncached run would have recomputed, and the deterministic forward writes
the same bits into them, so any drift is a sharing bug — not noise.
"""

import numpy as np
import pytest

from distributed_tensorflow_tpu.serve import ContinuousScheduler, ServeEngine
from distributed_tensorflow_tpu.serve.paged import (
    BlockAllocator,
    BlockExhaustedError,
    chain_block_keys,
)
from tests.helpers import fixed_reference


def _shared_prefix_requests(vocab, *, prefix_len=16, groups=2, n=8, seed=2):
    """n requests cycling over ``groups`` distinct system prompts, each
    with its own random tail (mixed lengths/horizons)."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, vocab, size=(prefix_len,), dtype=np.int32)
                for _ in range(groups)]
    reqs = []
    for i in range(n):
        tail_len = (4, 6, 5, 8)[i % 4]
        horizon = (5, 3, 4, 6)[i % 4]
        tail = rng.integers(0, vocab, size=(tail_len,), dtype=np.int32)
        reqs.append((np.concatenate([prefixes[i % groups], tail]), horizon))
    return reqs


@pytest.fixture(scope="module")
def gpt2_engine(request):
    mesh_dp = request.getfixturevalue("mesh_dp")
    eng = ServeEngine("gpt2", mesh=mesh_dp, preset="tiny")
    yield eng
    eng.close()


# ---------------------------------------------------------------------------
# Chained content keys
# ---------------------------------------------------------------------------

class TestChainBlockKeys:
    def test_full_blocks_only(self):
        toks = np.arange(11, dtype=np.int32)
        assert len(chain_block_keys(toks, 4)) == 2  # trailing 3 dropped
        assert chain_block_keys(toks[:3], 4) == []

    def test_deterministic_and_prefix_sensitive(self):
        toks = np.arange(12, dtype=np.int32)
        a = chain_block_keys(toks, 4)
        assert a == chain_block_keys(toks.copy(), 4)
        # mutating block 0 changes EVERY downstream key (chained hashes)
        other = toks.copy()
        other[0] += 1
        b = chain_block_keys(other, 4)
        assert all(x != y for x, y in zip(a, b))
        # mutating the last block leaves the earlier chain intact
        other = toks.copy()
        other[-1] += 1
        c = chain_block_keys(other, 4)
        assert c[:2] == a[:2] and c[2] != a[2]


# ---------------------------------------------------------------------------
# Refcounted allocator + prefix map: pure host-side unit tests
# ---------------------------------------------------------------------------

class TestPrefixAllocator:
    def test_refcounted_sharing_and_release(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        blocks = a.allocate(2, slot=0)
        keys = chain_block_keys(np.arange(8), 4)
        assert a.register_prefix(blocks, keys) == 2
        got = a.acquire_prefix(keys)
        assert got == blocks
        assert [a.ref_count(b) for b in blocks] == [2, 2]
        assert a.used_count == 2  # shared, not duplicated
        a.free(blocks)            # first holder retires
        assert [a.ref_count(b) for b in blocks] == [1, 1]
        assert a.used_count == 2
        a.free(blocks)            # last holder: park on the evictable LRU
        assert a.used_count == 0
        assert a.evictable_count == 2
        assert a.free_count == a.capacity - 2
        # still cached: a new request revives them without reallocation
        assert a.acquire_prefix(keys) == blocks

    def test_double_free_guard(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        blocks = a.allocate(2)
        a.free(blocks)
        with pytest.raises(ValueError, match="double free"):
            a.free([blocks[0]])
        # freeing a block that was never allocated is the same bug
        with pytest.raises(ValueError, match="double free"):
            a.free([5])
        # a parked (evictable) block has zero refs — freeing it again is
        # a double free too, not a silent LIFO corruption
        held = a.allocate(1)
        a.register_prefix(held, chain_block_keys(np.arange(4), 4))
        a.free(held)
        assert a.evictable_count == 1
        with pytest.raises(ValueError, match="double free"):
            a.free(held)

    def test_lru_eviction_under_pressure(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        all_blocks = a.allocate(7)
        keyed = {b: chain_block_keys(np.arange(i * 4, i * 4 + 4), 4)
                 for i, b in enumerate(all_blocks[:3])}
        for b, keys in keyed.items():
            a.register_prefix([b], keys)
        a.free(all_blocks)  # 3 park evictable (free order = LRU order), 4 free
        assert a.evictable_count == 3 and a.free_count == 4
        # need 5: four off the free list + ONE eviction — the LRU victim
        # is the first-parked registered block
        a.allocate(5)
        assert a.prefix_evictions == 1
        victim, survivor = all_blocks[0], all_blocks[1]
        assert a.lookup_prefix(keyed[victim]) == 0
        assert a.lookup_prefix(keyed[survivor]) == 1

    def test_exhaustion_counts_evictable_as_available(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        held = a.allocate(2)
        a.register_prefix(held, chain_block_keys(np.arange(8), 4))
        a.free(held)
        # 5 free + 2 evictable = 7 available; 8 is one too many
        with pytest.raises(BlockExhaustedError, match="only 7/7 free"):
            a.allocate(8)
        assert a.evictable_count == 2  # the failed call evicted nothing
        assert len(a.allocate(7)) == 7  # full capacity via eviction
        assert a.prefix_evictions == 2

    def test_invalidate_returns_evictable_to_free_list(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        live = a.allocate(1)
        parked = a.allocate(2)
        keys = chain_block_keys(np.arange(12), 4)
        a.register_prefix(live + parked, keys)
        a.free(parked)
        assert a.invalidate_prefix_cache() == 3
        assert a.cached_block_count == 0
        assert a.evictable_count == 0
        assert a.free_count == a.capacity - 1  # the live block stays out
        assert a.lookup_prefix(keys) == 0
        a.free(live)  # unregistered now: straight back to the free list
        assert a.free_count == a.capacity

    def test_register_requires_live_block(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        with pytest.raises(ValueError, match="unallocated"):
            a.register_prefix([3], chain_block_keys(np.arange(4), 4))

    def test_register_is_idempotent_first_writer_wins(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        keys = chain_block_keys(np.arange(4), 4)
        first, second = a.allocate(1), a.allocate(1)
        assert a.register_prefix(first, keys) == 1
        assert a.register_prefix(first, keys) == 0   # already registered
        assert a.register_prefix(second, keys) == 0  # key taken: skipped
        assert a.acquire_prefix(keys) == first
        a.free(first)  # drop the acquire's ref; holders still live

    def test_stats_surface(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        held = a.allocate(2)
        a.register_prefix(held, chain_block_keys(np.arange(8), 4))
        a.free(held)
        s = a.stats()
        assert s["blocks_in_use"] == 0.0
        assert s["blocks_evictable"] == 2.0
        assert s["prefix_cached_blocks"] == 2.0
        assert s["prefix_evictions"] == 0.0


# ---------------------------------------------------------------------------
# Parity oracle: prefix_cache on == off, token for token
# ---------------------------------------------------------------------------

def _run_scheduler(engine, reqs, *, sequential=False, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_total_len", 32)
    kw.setdefault("cache_mode", "paged")
    kw.setdefault("block_size", 4)
    with ContinuousScheduler(engine, **kw) as sched:
        if sequential:
            outs = [sched.submit(p, max_new_tokens=m).result(timeout=300)
                    for p, m in reqs]
        else:
            futs = [sched.submit(p, max_new_tokens=m) for p, m in reqs]
            outs = [f.result(timeout=300) for f in futs]
        stats = sched.stats()
    return outs, stats


class TestPrefixParity:
    def test_shared_prefix_traffic_parity_mesh_dp(self, gpt2_engine):
        """THE acceptance property: the same shared-prefix mix, with and
        without the cache, produces identical greedy streams — and the
        cached run actually hit."""
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _shared_prefix_requests(vocab, n=10)
        off, _ = _run_scheduler(gpt2_engine, reqs, prefix_cache=False)
        on, s = _run_scheduler(gpt2_engine, reqs, prefix_cache=True)
        for a, b in zip(off, on):
            np.testing.assert_array_equal(a, b)
        assert s["prefix_hits"] > 0
        assert s["prefill_tokens_skipped"] > 0
        assert 0.0 < s["prefix_hit_rate"] <= 1.0
        assert s["blocks_in_use"] == 0.0  # all references released

    def test_cow_divergence_shares_then_splits(self, gpt2_engine):
        """Two requests agree for 4 blocks then diverge inside block 5;
        sequential submission guarantees the second maps the shared
        blocks and recomputes the divergent one privately (COW) — both
        streams must match the fixed-batch reference."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(3)
        base = rng.integers(0, vocab, size=(22,), dtype=np.int32)
        fork = base.copy()
        fork[18] = (fork[18] + 1) % vocab  # diverge inside block 4
        reqs = [(base, 5), (fork, 5)]
        outs, s = _run_scheduler(gpt2_engine, reqs, sequential=True,
                                 prefix_cache=True)
        for (prompt, horizon), out in zip(reqs, outs):
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, horizon))
        assert s["prefix_hits"] == 4.0  # blocks 0-3 shared, block 4 not

    def test_block_aligned_prompt_recomputes_last_block(self, gpt2_engine):
        """A prompt the cache covers ENTIRELY still prefills its final
        block (prefill must emit the first sampled token), writing a
        private copy — identical identical-prompt streams prove the
        shared copy was never clobbered."""
        vocab = gpt2_engine.module.cfg.vocab_size
        prompt = np.random.default_rng(4).integers(
            0, vocab, size=(16,), dtype=np.int32)  # exactly 4 blocks
        reqs = [(prompt, 6), (prompt, 6), (prompt, 6)]
        outs, s = _run_scheduler(gpt2_engine, reqs, sequential=True,
                                 prefix_cache=True)
        ref = fixed_reference(gpt2_engine, prompt, 6)
        for out in outs:
            np.testing.assert_array_equal(out, ref)
        assert s["prefix_hits"] == 6.0  # 3 mappable blocks x 2 hits

    @pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
    def test_suffix_prefill_leaves_shared_blocks_byte_identical(
            self, gpt2_engine, kv_dtype):
        """What the cache rests on, at the engine: a suffix prefill from a
        block-aligned offset scatters at positions >= the offset only, so
        the blocks it shares stay byte for byte what the first request
        wrote (every pool leaf, scales included), while its gather reads
        them: its first token is the one a full prefill picks."""
        import jax
        from distributed_tensorflow_tpu.models.gpt2 import PagedKVConfig

        pcfg = PagedKVConfig(block_size=4, num_blocks=17, kv_dtype=kv_dtype)
        vocab = gpt2_engine.module.cfg.vocab_size
        prompt = np.random.default_rng(8).integers(
            0, vocab, size=(1, 11), dtype=np.int32)
        tables = np.zeros((8, 4), np.int32)
        tables[1] = [3, 4, 5, 0]        # the first request, whole prompt
        tables[6] = [3, 4, 9, 0]        # shares blocks 3 and 4
        tables[2] = [10, 11, 12, 0]     # control: the same prompt in full
        cache = gpt2_engine.init_paged_cache(8, 16, paged=pcfg)
        first, cache = gpt2_engine.prefill_into_slots(
            cache, prompt, [1], paged=pcfg, block_tables=tables)
        before = jax.device_get(cache)["blocks"]
        suffix, cache = gpt2_engine.prefill_into_slots(
            cache, prompt[:, 8:], [6], paged=pcfg, block_tables=tables,
            start_offsets=[8])
        full, cache = gpt2_engine.prefill_into_slots(
            cache, prompt, [2], paged=pcfg, block_tables=tables)
        after = jax.device_get(cache)["blocks"]
        pools = [n for n in after if n != "cache_index"]
        assert len(pools) == (4 if kv_dtype == "int8" else 2)
        for name in pools:
            for block in (3, 4, 5):     # shared prefix + the first's tail
                np.testing.assert_array_equal(
                    after[name][:, block], before[name][:, block])
            assert np.any(after[name][:, 9] != before[name][:, 9])
            # the suffix's own block holds what the full prefill wrote
            np.testing.assert_array_equal(
                after[name][:, 9, :3], after[name][:, 12, :3])
        np.testing.assert_array_equal(np.asarray(suffix), np.asarray(full))
        np.testing.assert_array_equal(np.asarray(first), np.asarray(full))
        np.testing.assert_array_equal(after["cache_index"][:, [1, 6, 2]],
                                      [[11, 11, 11]] * 2)

    def test_parity_under_tensor_parallel_mesh(self, mesh_2d):
        """Same oracle on data=4 x tensor=2: cached-block K/V is sharded
        over the tensor axis exactly like freshly-prefilled K/V."""
        with ServeEngine("gpt2", mesh=mesh_2d, preset="tiny") as eng:
            vocab = eng.module.cfg.vocab_size
            reqs = _shared_prefix_requests(vocab, n=6, seed=9)
            off, _ = _run_scheduler(eng, reqs, prefix_cache=False)
            on, s = _run_scheduler(eng, reqs, prefix_cache=True)
            for a, b in zip(off, on):
                np.testing.assert_array_equal(a, b)
            assert s["prefix_hits"] > 0

    def test_per_shard_pools_compose(self, gpt2_engine):
        """per_shard_kv + prefix_cache: each shard keys its own map, so
        hits only happen shard-locally — sequential LIFO slot reuse lands
        same-prefix requests on the same shard, and streams still match
        the uncached run."""
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _shared_prefix_requests(vocab, groups=1, n=4, seed=5)
        off, _ = _run_scheduler(gpt2_engine, reqs, sequential=True,
                                num_slots=8, per_shard_kv=True,
                                prefix_cache=False)
        on, s = _run_scheduler(gpt2_engine, reqs, sequential=True,
                               num_slots=8, per_shard_kv=True,
                               prefix_cache=True)
        for a, b in zip(off, on):
            np.testing.assert_array_equal(a, b)
        assert s["prefix_hits"] > 0
        assert s["num_shards"] > 1.0


# ---------------------------------------------------------------------------
# Eviction under pressure + hot-reload invalidation
# ---------------------------------------------------------------------------

class TestPrefixEviction:
    def test_eviction_under_pressure_keeps_parity(self, gpt2_engine):
        """A pool too small to cache every retired prompt evicts LRU
        zero-ref blocks to serve new admissions — backpressure behaviour
        (admission, never mid-decode failure) and streams stay identical
        to the uncached run, and a re-visit of an evicted prefix simply
        misses and recomputes."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(6)
        distinct = [(rng.integers(0, vocab, size=(8,), dtype=np.int32), 5)
                    for _ in range(6)]
        reqs = distinct + [distinct[0]]  # revisit the first (evicted) prefix
        # 9 usable blocks; each request's worst case is blocks_for(12) = 3
        # and each retirement parks 2 registered prompt blocks.
        kw = dict(max_total_len=16, num_blocks=10, sequential=True)
        off, _ = _run_scheduler(gpt2_engine, reqs, prefix_cache=False, **kw)
        on, s = _run_scheduler(gpt2_engine, reqs, prefix_cache=True, **kw)
        for a, b in zip(off, on):
            np.testing.assert_array_equal(a, b)
        assert s["prefix_evictions"] > 0.0
        assert s["blocks_high_water"] <= 9.0
        for (prompt, horizon), out in zip(reqs, on):
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, horizon))

    def test_hot_reload_invalidates_cache(self, gpt2_engine):
        """A staged weight generation drops every cached key (cached K/V
        is params-dependent): the same prefix misses right after the
        swap, then caches again under the new generation."""
        vocab = gpt2_engine.module.cfg.vocab_size
        prompt = np.random.default_rng(7).integers(
            0, vocab, size=(18,), dtype=np.int32)
        with ContinuousScheduler(gpt2_engine, num_slots=4, max_total_len=32,
                                 cache_mode="paged", block_size=4,
                                 prefix_cache=True) as sched:
            sched.submit(prompt, max_new_tokens=4).result(timeout=300)
            sched.submit(prompt, max_new_tokens=4).result(timeout=300)
            hits_before = sched.stats()["prefix_hits"]
            assert hits_before == 4.0
            sched.update_params(gpt2_engine.params, generation=123)
            fut = sched.submit(prompt, max_new_tokens=4)
            np.testing.assert_array_equal(
                fut.result(timeout=300),
                fixed_reference(gpt2_engine, prompt, 4))
            assert fut.generation == 123
            # the post-swap admission found an empty map: no new hits...
            assert sched.stats()["prefix_hits"] == hits_before
            # ...but re-registered, so the NEXT one hits again
            sched.submit(prompt, max_new_tokens=4).result(timeout=300)
            assert sched.stats()["prefix_hits"] == hits_before + 4.0

    def test_prefix_cache_requires_paged_mode(self, gpt2_engine):
        with pytest.raises(ValueError, match="paged"):
            ContinuousScheduler(gpt2_engine, cache_mode="dense",
                                prefix_cache=True, start=False)
