"""Continuous-batching tests: slot-mode cache semantics in the model, the
engine's slot APIs (prefill_into_slots / decode_slots over one resident
cache), and the ContinuousScheduler's contract — token-for-token parity
with the fixed-batch path on mixed traffic, slot reuse without stale K/V,
overload rejection, sampling, and the iteration-level batcher front.

Parity runs on BOTH acceptance meshes: the pure data-parallel mesh and
data=4 x tensor=2 (params sharded by gpt2_rules, resident cache by
gpt2_cache_rules).  Greedy decode is deterministic on CPU, so parity is
exact array equality, not tolerance.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.serve import (
    ContinuousScheduler,
    DynamicBatcher,
    ServeEngine,
    ServeOverloadedError,
)
from tests.helpers import fixed_reference


def _mixed_requests(vocab, n=20, seed=1):
    """Mixed prompt lengths AND mixed horizons — the traffic continuous
    batching exists for."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        length = (4, 6, 9)[i % 3]
        horizon = (2, 5, 3, 7)[i % 4]
        reqs.append((rng.integers(0, vocab, size=(length,), dtype=np.int32),
                     horizon))
    return reqs


# ---------------------------------------------------------------------------
# Model layer: slot_ids threading through the decode cache
# ---------------------------------------------------------------------------

def _tiny_gpt2(**kw):
    from distributed_tensorflow_tpu.models.gpt2 import GPT2, GPT2Config

    cfg = GPT2Config.tiny(dtype=jnp.float32, **kw)
    return GPT2(cfg), cfg


class TestSlotModeCache:
    def test_slot_cache_index_is_per_slot_vector(self):
        model, _ = _tiny_gpt2()
        num_slots, T = 4, 8
        vs = jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((num_slots, T), jnp.int32),
            decode=True, slot_ids=jnp.arange(num_slots)))
        flat = {"/".join(str(k.key) for k in path): leaf
                for path, leaf in jax.tree_util.tree_flatten_with_path(
                    vs["cache"])[0]}
        idx = next(v for k, v in flat.items() if "cache_index" in k)
        # scan stacks the per-layer caches: (L, num_slots) not scalar (L,)
        assert idx.shape[-1] == num_slots

    def test_slot_subset_prefill_matches_full_forward(self):
        """Prefill into a SUBSET of slots at arbitrary ids; logits must
        match the plain forward, and untouched slots' index rows stay 0."""
        model, cfg = _tiny_gpt2()
        num_slots, T = 8, 6
        tokens = np.asarray(jax.random.randint(
            jax.random.key(1), (2, T), 0, cfg.vocab_size))
        params = model.init(jax.random.key(0), tokens)["params"]
        full = model.apply({"params": params}, jnp.asarray(tokens))

        shapes = jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((num_slots, T), jnp.int32),
            decode=True, slot_ids=jnp.arange(num_slots)))["cache"]
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        slot_ids = jnp.asarray([5, 2])  # non-contiguous, out of order
        logits, vs = model.apply(
            {"params": params, "cache": cache}, jnp.asarray(tokens),
            decode=True, slot_ids=slot_ids, mutable=["cache"])
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                                   rtol=1e-4, atol=1e-4)
        flat = {"/".join(str(k.key) for k in path): leaf
                for path, leaf in jax.tree_util.tree_flatten_with_path(
                    vs["cache"])[0]}
        idx = np.asarray(next(v for k, v in flat.items()
                              if "cache_index" in k))
        assert (idx[:, [5, 2]] == T).all()
        untouched = [s for s in range(num_slots) if s not in (5, 2)]
        assert (idx[:, untouched] == 0).all()

    def test_slot_ids_without_decode_rejected(self):
        model, _ = _tiny_gpt2()
        with pytest.raises(ValueError, match="slot_ids"):
            model.init(jax.random.key(0), jnp.zeros((2, 4), jnp.int32),
                       slot_ids=jnp.arange(2))


# ---------------------------------------------------------------------------
# Engine layer: resident slot cache APIs
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gpt2_engine(request):
    mesh_dp = request.getfixturevalue("mesh_dp")
    eng = ServeEngine("gpt2", mesh=mesh_dp, preset="tiny")
    yield eng
    eng.close()


class TestEngineSlotAPIs:
    def test_init_slot_cache_validates_geometry(self, gpt2_engine):
        with pytest.raises(ValueError, match="multiple"):
            gpt2_engine.init_slot_cache(3, 16)  # dp=8 on the 8-way mesh
        n_pos = gpt2_engine.module.cfg.n_positions
        with pytest.raises(ValueError, match="n_positions"):
            gpt2_engine.init_slot_cache(8, n_pos + 1)

    def test_prefill_then_decode_matches_generate(self, gpt2_engine):
        """Drive the slot APIs by hand — per-slot prefill at staggered
        times, then shared (num_slots, 1) steps — and compare each slot's
        stream to the fixed-batch generate, token for token."""
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, vocab, size=(n,), dtype=np.int32)
                   for n in (5, 7, 5)]
        cache = gpt2_engine.init_slot_cache(8, 24)
        last = np.zeros((8, 1), np.int32)
        streams = {s: [] for s in (6, 1, 3)}
        for prompt, slot in zip(prompts, (6, 1, 3)):
            tok, cache = gpt2_engine.prefill_into_slots(
                cache, prompt[None, :], [slot])
            streams[slot].append(int(np.asarray(jax.device_get(tok))[0]))
            last[slot, 0] = streams[slot][-1]
        active = np.zeros((8,), bool)
        active[[6, 1, 3]] = True
        for _ in range(4):
            tok, cache = gpt2_engine.decode_slots(cache, last, active)
            toks = np.asarray(jax.device_get(tok))
            for slot in (6, 1, 3):
                streams[slot].append(int(toks[slot]))
                last[slot, 0] = toks[slot]
        for prompt, slot in zip(prompts, (6, 1, 3)):
            ref = fixed_reference(gpt2_engine, prompt, 5)
            np.testing.assert_array_equal(np.asarray(streams[slot]), ref)

    def test_inactive_slots_do_not_advance(self, gpt2_engine):
        """The active-mask contract: a decode step must not move an
        inactive slot's cache_index/position rows."""
        vocab = gpt2_engine.module.cfg.vocab_size
        prompt = np.arange(4, dtype=np.int32) % vocab
        cache = gpt2_engine.init_slot_cache(8, 16)
        _, cache = gpt2_engine.prefill_into_slots(cache, prompt[None, :], [0])
        _, cache = gpt2_engine.prefill_into_slots(cache, prompt[None, :], [5])

        def index_rows(c):
            flat = {"/".join(str(k.key) for k in path): leaf
                    for path, leaf in jax.tree_util.tree_flatten_with_path(
                        c)[0]}
            return np.asarray(next(v for k, v in flat.items()
                                   if "cache_index" in k))

        before = index_rows(cache)
        active = np.zeros((8,), bool)
        active[0] = True
        _, cache = gpt2_engine.decode_slots(
            cache, np.zeros((8, 1), np.int32), active)
        after = index_rows(cache)
        assert (after[:, 0] == before[:, 0] + 1).all()   # active advanced
        assert (after[:, 5] == before[:, 5]).all()       # inactive frozen
        assert (after[:, 1] == 0).all()                  # empty untouched


# ---------------------------------------------------------------------------
# ContinuousScheduler: parity, reuse, overload, sampling
# ---------------------------------------------------------------------------

class TestContinuousScheduler:
    def test_mixed_traffic_parity_with_fixed_batch(self, gpt2_engine):
        """THE acceptance property: greedy continuous decode of mixed-length
        mixed-horizon requests is token-for-token identical to the
        fixed-batch path — more requests than slots, so every slot is
        reused at least once (stale-K/V hygiene is load-bearing here)."""
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab, n=20)
        with ContinuousScheduler(gpt2_engine, num_slots=8,
                                 max_total_len=32) as sched:
            futs = [sched.submit(p, max_new_tokens=m) for p, m in reqs]
            outs = [f.result(timeout=300) for f in futs]
            s = sched.stats()
        assert s["completed"] == float(len(reqs))
        assert s["retired"] == float(len(reqs))
        assert s["iterations"] > 0
        assert 0.0 < s["slot_occupancy"] <= 1.0
        assert s["ttft_p50_ms"] > 0.0
        for (prompt, horizon), out in zip(reqs, outs):
            assert out.shape == (horizon,) and out.dtype == np.int32
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, horizon))

    def test_parity_under_tensor_parallel_mesh(self, mesh_2d):
        """Same parity on the data=4 x tensor=2 mesh (the --tensor=2
        acceptance configuration): slot rows shard over data, heads over
        tensor."""
        with ServeEngine("gpt2", mesh=mesh_2d, preset="tiny") as eng:
            vocab = eng.module.cfg.vocab_size
            reqs = _mixed_requests(vocab, n=10, seed=7)
            with ContinuousScheduler(eng, num_slots=4,
                                     max_total_len=32) as sched:
                futs = [sched.submit(p, max_new_tokens=m) for p, m in reqs]
                outs = [f.result(timeout=300) for f in futs]
            for (prompt, horizon), out in zip(reqs, outs):
                np.testing.assert_array_equal(
                    out, fixed_reference(eng, prompt, horizon))

    def test_eos_retires_slot_early(self, gpt2_engine):
        """A request whose greedy stream hits its eos token retires at the
        eos, shorter than its horizon."""
        vocab = gpt2_engine.module.cfg.vocab_size
        prompt = np.arange(6, dtype=np.int32) % vocab
        ref = fixed_reference(gpt2_engine, prompt, 8)
        eos = int(ref[3])  # force an eos hit mid-stream
        cut = int(np.flatnonzero(ref == eos)[0]) + 1  # first occurrence
        with ContinuousScheduler(gpt2_engine, num_slots=8,
                                 max_total_len=32) as sched:
            out = sched.submit(prompt, max_new_tokens=8,
                               eos_token=eos).result(timeout=300)
        assert len(out) == cut < 8
        assert out[-1] == eos
        np.testing.assert_array_equal(out, ref[:cut])

    def test_overload_rejection_and_close(self, gpt2_engine):
        """Unstarted loop -> the admission queue fills to its bound and
        rejects; close() fails the stranded futures."""
        prompt = np.zeros((4,), np.int32)
        cold = ContinuousScheduler(gpt2_engine, num_slots=8,
                                   max_total_len=16, max_queue_size=3,
                                   start=False)
        futs = [cold.submit(prompt, max_new_tokens=2) for _ in range(3)]
        with pytest.raises(ServeOverloadedError):
            cold.submit(prompt, max_new_tokens=2)
        assert cold.stats()["rejected"] == 1.0
        cold.close(timeout=0.1)
        for f in futs:
            with pytest.raises(RuntimeError):
                f.result(timeout=5)
        with pytest.raises(RuntimeError):
            cold.submit(prompt, max_new_tokens=2)

    def test_submit_validates_total_length(self, gpt2_engine):
        with ContinuousScheduler(gpt2_engine, num_slots=8,
                                 max_total_len=16) as sched:
            with pytest.raises(ValueError, match="max_total_len"):
                sched.submit(np.zeros((12,), np.int32), max_new_tokens=8)
            with pytest.raises(ValueError, match="max_new_tokens"):
                sched.submit(np.zeros((4,), np.int32), max_new_tokens=0)

    def test_rejects_model_without_decode_cache(self, mesh_dp):
        with ServeEngine("mnist", mesh=mesh_dp, batch_size=32) as eng:
            with pytest.raises(ValueError, match="decode"):
                ContinuousScheduler(eng, start=False)


class TestSampling:
    def test_top_k_one_equals_greedy(self, gpt2_engine):
        """temperature > 0 with top_k=1 can only pick the argmax — the
        sampling path must reproduce the greedy stream exactly."""
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab, n=6, seed=11)
        with ContinuousScheduler(gpt2_engine, num_slots=8, max_total_len=32,
                                 temperature=0.7, top_k=1) as sched:
            futs = [sched.submit(p, max_new_tokens=m) for p, m in reqs]
            outs = [f.result(timeout=300) for f in futs]
        for (prompt, horizon), out in zip(reqs, outs):
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, horizon))

    def test_sampled_generate_valid_and_seeded(self, gpt2_engine):
        vocab = gpt2_engine.module.cfg.vocab_size
        prompts = np.asarray(jax.random.randint(
            jax.random.key(6), (8, 5), 0, vocab))
        rng = jax.random.key(9)
        a = gpt2_engine.generate(prompts, 6, temperature=0.9, top_k=8,
                                 rng=rng)
        b = gpt2_engine.generate(prompts, 6, temperature=0.9, top_k=8,
                                 rng=rng)
        assert a.shape == (8, 6)
        assert (a >= 0).all() and (a < vocab).all()
        np.testing.assert_array_equal(a, b)  # same key -> same stream


# ---------------------------------------------------------------------------
# DynamicBatcher iteration-level front
# ---------------------------------------------------------------------------

class TestIterationLevelBatcher:
    def test_streams_to_scheduler_with_same_surface(self, gpt2_engine):
        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab, n=6, seed=5)
        sched = ContinuousScheduler(gpt2_engine, num_slots=8,
                                    max_total_len=32)
        with DynamicBatcher(iteration_level=True, scheduler=sched) as b:
            futs = [b.submit((p, m)) for p, m in reqs]
            outs = [f.result(timeout=300) for f in futs]
            s = b.stats()
        assert "slot_occupancy" in s  # the scheduler's snapshot
        assert s["completed"] == float(len(reqs))
        for (prompt, horizon), out in zip(reqs, outs):
            np.testing.assert_array_equal(
                out, fixed_reference(gpt2_engine, prompt, horizon))

    def test_iteration_level_requires_scheduler(self):
        with pytest.raises(ValueError, match="scheduler"):
            DynamicBatcher(iteration_level=True)
        with pytest.raises(ValueError, match="run_batch"):
            DynamicBatcher(lambda p: p, iteration_level=True,
                           scheduler=object())

    def test_closed_batcher_rejects_submit(self, gpt2_engine):
        sched = ContinuousScheduler(gpt2_engine, num_slots=8,
                                    max_total_len=16)
        b = DynamicBatcher(iteration_level=True, scheduler=sched)
        b.close()
        with pytest.raises(RuntimeError):
            b.submit(np.zeros((2,), np.int32))


# ---------------------------------------------------------------------------
# ServeMonitorHook: iteration-level counters on the export surface
# ---------------------------------------------------------------------------

class TestContinuousMonitorExport:
    def test_hook_exports_slot_counters(self, gpt2_engine, caplog):
        import logging

        from distributed_tensorflow_tpu.obs import ServeMonitorHook

        vocab = gpt2_engine.module.cfg.vocab_size
        reqs = _mixed_requests(vocab, n=4, seed=13)
        with ContinuousScheduler(gpt2_engine, num_slots=8,
                                 max_total_len=32) as sched:
            hook = ServeMonitorHook(sched, every_steps=1)
            futs = [sched.submit(p, max_new_tokens=m) for p, m in reqs]
            for f in futs:
                f.result(timeout=300)
            m = hook.metrics()
            with caplog.at_level(
                    logging.INFO,
                    logger="distributed_tensorflow_tpu.obs.serve"):
                logged = hook.log(4)
        for key in ("serve_slot_occupancy", "serve_admissions_per_iter",
                    "serve_retirements_per_iter", "serve_ttft_p50_ms",
                    "serve_ttft_p99_ms", "serve_tpot_mean_ms",
                    "serve_iterations", "serve_num_slots"):
            assert key in m, m
        assert logged["serve_completed"] == 4.0
        assert any("occupancy=" in r.message and "ttft_p50=" in r.message
                   for r in caplog.records)
