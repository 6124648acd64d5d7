"""serve/ subsystem tests: KV-cache decode parity, dynamic batcher
behavior (full-batch flush, timeout flush, rejection, out-of-order
completion), engine restore/classify paths, checkpoint teardown surface,
and the ServeMonitorHook export.

All run on the forced 8-CPU-device platform from conftest.py; the sharded
parity test uses the data=4 x tensor=2 mesh — the ``--tensor=2`` acceptance
configuration.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.serve import (
    DynamicBatcher,
    ServeEngine,
    ServeOverloadedError,
    pad_rows,
)


# ---------------------------------------------------------------------------
# DynamicBatcher
# ---------------------------------------------------------------------------

class _Recorder:
    """run_batch stub that records every dispatched batch."""

    def __init__(self, delay_s=0.0, fail=False):
        self.batches = []
        self.delay_s = delay_s
        self.fail = fail
        self.lock = threading.Lock()

    def __call__(self, payloads):
        with self.lock:
            self.batches.append(list(payloads))
        if self.delay_s:
            time.sleep(self.delay_s)
        if self.fail:
            raise ValueError("engine exploded")
        return [p * 10 for p in payloads]


class TestDynamicBatcher:
    def test_full_batch_flushes_immediately(self):
        rec = _Recorder()
        # Long timeout: only the full-bucket rule can flush this fast.
        with DynamicBatcher(rec, max_batch_size=4,
                            batch_timeout_ms=10_000) as b:
            futs = [b.submit(i) for i in range(4)]
            results = [f.result(timeout=5) for f in futs]
        assert results == [0, 10, 20, 30]
        assert [len(x) for x in rec.batches] == [4]

    def test_timeout_flushes_partial_batch(self):
        rec = _Recorder()
        with DynamicBatcher(rec, max_batch_size=8,
                            batch_timeout_ms=30) as b:
            t0 = time.monotonic()
            f = b.submit(7)
            assert f.result(timeout=5) == 70
            waited = time.monotonic() - t0
        # Flushed by the timeout (not full, not close()).
        assert rec.batches == [[7]]
        assert waited >= 0.025

    def test_rejection_under_overload(self):
        release = threading.Event()

        def blocked(payloads):
            release.wait(10)
            return payloads

        b = DynamicBatcher(blocked, max_batch_size=2, batch_timeout_ms=1,
                           max_queue_size=3)
        try:
            for i in range(2):
                b.submit(i)
            # Give the scheduler time to move the first batch in-flight,
            # then fill the queue to its bound.
            time.sleep(0.05)
            for i in range(3):
                b.submit(i)
            with pytest.raises(ServeOverloadedError):
                b.submit(99)
            assert b.stats()["rejected"] == 1.0
        finally:
            release.set()
            b.close()

    def test_out_of_order_completion_full_bucket_first(self):
        order = []
        lock = threading.Lock()

        def run(payloads):
            with lock:
                order.append(list(payloads))
            return payloads

        # Bucket by parity.  Submit ONE odd request first, then a FULL even
        # bucket: the full bucket must flush ahead of the older partial one.
        b = DynamicBatcher(run, max_batch_size=3, batch_timeout_ms=200,
                           bucket_fn=lambda p: p % 2)
        try:
            f_odd = b.submit(1)
            time.sleep(0.02)
            evens = [b.submit(p) for p in (0, 2, 4)]
            assert [f.result(timeout=5) for f in evens] == [0, 2, 4]
            assert f_odd.result(timeout=5) == 1
        finally:
            b.close()
        assert order[0] == [0, 2, 4], order  # younger full bucket won
        assert order[1] == [1], order

    def test_buckets_never_mix(self):
        rec = _Recorder()
        with DynamicBatcher(rec, max_batch_size=8, batch_timeout_ms=10,
                            bucket_fn=lambda p: p % 2) as b:
            futs = [b.submit(i) for i in range(6)]
            for f in futs:
                f.result(timeout=5)
        for batch in rec.batches:
            assert len({p % 2 for p in batch}) == 1, rec.batches

    def test_concurrent_clients_get_their_own_results(self):
        rec = _Recorder()
        results = {}
        lock = threading.Lock()
        with DynamicBatcher(rec, max_batch_size=4, batch_timeout_ms=2) as b:
            def client(base):
                for i in range(base, base + 25):
                    r = b.submit(i).result(timeout=10)
                    with lock:
                        results[i] = r

            threads = [threading.Thread(target=client, args=(c * 100,))
                       for c in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        assert len(results) == 100
        assert all(v == k * 10 for k, v in results.items())

    def test_engine_error_propagates_to_futures(self):
        rec = _Recorder(fail=True)
        with DynamicBatcher(rec, max_batch_size=2, batch_timeout_ms=1) as b:
            f1, f2 = b.submit(1), b.submit(2)
            with pytest.raises(ValueError, match="engine exploded"):
                f1.result(timeout=5)
            with pytest.raises(ValueError):
                f2.result(timeout=5)
            assert b.stats()["failed"] == 2.0

    def test_close_fails_pending_and_rejects_new(self):
        release = threading.Event()

        def blocked(payloads):
            release.wait(10)
            return payloads

        b = DynamicBatcher(blocked, max_batch_size=1, batch_timeout_ms=1,
                           max_queue_size=8)
        inflight = b.submit(0)
        time.sleep(0.05)  # scheduler now blocked inside run_batch
        pending = b.submit(1)
        # Worker still blocked: request 1 is never dispatched, so close()
        # must fail its future rather than leave the caller hanging.
        b.close(timeout=0.2)
        b.close()  # idempotent
        with pytest.raises(RuntimeError):
            pending.result(timeout=5)
        with pytest.raises(RuntimeError):
            b.submit(2)
        release.set()  # the in-flight batch still completes normally
        assert inflight.result(timeout=5) == 0

    def test_stats_counters(self):
        rec = _Recorder()
        with DynamicBatcher(rec, max_batch_size=2, batch_timeout_ms=2) as b:
            futs = [b.submit(i) for i in range(6)]
            for f in futs:
                f.result(timeout=5)
            s = b.stats()
        assert s["submitted"] == 6.0
        assert s["completed"] == 6.0
        assert s["queue_depth"] == 0.0
        assert s["batches"] >= 3.0
        assert 1.0 <= s["avg_batch_occupancy"] <= 2.0
        assert s["p50_latency_ms"] >= 0.0
        assert s["p99_latency_ms"] >= s["p50_latency_ms"]


# ---------------------------------------------------------------------------
# pad_rows
# ---------------------------------------------------------------------------

class TestPadRows:
    def test_pads_by_repeating_last_row(self):
        a = np.arange(6, dtype=np.int32).reshape(3, 2)
        out = pad_rows(a, 5)
        assert out.shape == (5, 2)
        np.testing.assert_array_equal(out[3], a[-1])
        np.testing.assert_array_equal(out[4], a[-1])

    def test_noop_and_overflow(self):
        a = np.zeros((4, 2))
        assert pad_rows(a, 4) is a
        with pytest.raises(ValueError):
            pad_rows(a, 2)


# ---------------------------------------------------------------------------
# KV-cache decode parity (satellite c)
# ---------------------------------------------------------------------------

def _tiny_gpt2(**kw):
    from distributed_tensorflow_tpu.models.gpt2 import GPT2, GPT2Config

    cfg = GPT2Config.tiny(dtype=jnp.float32, **kw)
    return GPT2(cfg), cfg


def _fresh_cache(model, B, T):
    """Zeroed decode cache for B rows of up to T tokens.  ``init`` returns
    POST-call variables (cache_index/position already advanced past the init
    input), so zero the whole tree — what the engine's ``init_cache`` does
    via eval_shape."""
    vs = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((B, T), jnp.int32), decode=True))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), vs["cache"])


def _incremental_logits(model, params, cache, tokens, prefill):
    """Prefill ``prefill`` tokens, then decode one token at a time;
    concatenated logits over the whole sequence."""
    @jax.jit
    def step(params, cache, tok):
        logits, vs = model.apply(
            {"params": params, "cache": cache}, tok,
            decode=True, mutable=["cache"])
        return logits, vs["cache"]

    T = tokens.shape[1]
    logits, cache = step(params, cache, tokens[:, :prefill])
    outs = [logits]
    for i in range(prefill, T):
        logits, cache = step(params, cache, tokens[:, i:i + 1])
        outs.append(logits)
    return jnp.concatenate(outs, axis=1)


class TestDecodeParity:
    def test_incremental_matches_full_forward(self):
        model, cfg = _tiny_gpt2()
        B, T = 2, 10
        tokens = jax.random.randint(
            jax.random.key(1), (B, T), 0, cfg.vocab_size)
        params = model.init(jax.random.key(0), tokens)["params"]
        full = model.apply({"params": params}, tokens)
        cache = _fresh_cache(model, B, T)
        inc = _incremental_logits(model, params, cache, tokens, prefill=4)
        np.testing.assert_allclose(
            np.asarray(inc), np.asarray(full), rtol=1e-4, atol=1e-4)

    def test_prefill_only_matches_full_forward(self):
        model, cfg = _tiny_gpt2()
        B, T = 2, 8
        tokens = jax.random.randint(
            jax.random.key(2), (B, T), 0, cfg.vocab_size)
        params = model.init(jax.random.key(0), tokens)["params"]
        full = model.apply({"params": params}, tokens)
        cache = _fresh_cache(model, B, T)
        pre, _ = model.apply(
            {"params": params, "cache": cache}, tokens,
            decode=True, mutable=["cache"])
        np.testing.assert_allclose(
            np.asarray(pre), np.asarray(full), rtol=1e-4, atol=1e-4)

    def test_parity_under_tensor_parallel_mesh(self, mesh_2d):
        """The --tensor=2 acceptance case: params sharded by gpt2_rules,
        cache by gpt2_cache_rules, on the data=4 x tensor=2 CPU mesh."""
        from distributed_tensorflow_tpu.models.gpt2 import (
            gpt2_cache_rules,
            gpt2_rules,
        )
        from distributed_tensorflow_tpu.parallel.sharding import (
            apply_shardings,
            batch_sharding,
        )

        model, cfg = _tiny_gpt2()
        B, T = 4, 12
        tokens = np.asarray(jax.random.randint(
            jax.random.key(3), (B, T), 0, cfg.vocab_size))
        params = model.init(jax.random.key(0), tokens)["params"]
        params = apply_shardings(
            params, gpt2_rules().shardings_for(mesh_2d, params))
        tok_dev = jax.device_put(tokens, batch_sharding(mesh_2d))
        full = jax.jit(lambda p, t: model.apply({"params": p}, t))(
            params, tok_dev)

        cache_shapes = jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((B, T), jnp.int32),
            decode=True))["cache"]
        cache = jax.jit(
            lambda: jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), cache_shapes),
            out_shardings=gpt2_cache_rules().shardings_for(
                mesh_2d, cache_shapes),
        )()
        inc = _incremental_logits(model, params, cache, tok_dev, prefill=5)
        np.testing.assert_allclose(
            np.asarray(inc), np.asarray(full), rtol=2e-4, atol=2e-4)

    def test_cache_rules_shard_heads_over_tensor(self, mesh_2d):
        from distributed_tensorflow_tpu.models.gpt2 import gpt2_cache_rules

        model, cfg = _tiny_gpt2()
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((2, 8), jnp.int32),
            decode=True))["cache"]
        sh = gpt2_cache_rules().shardings_for(mesh_2d, shapes)
        flat = {"/".join(str(k.key) for k in path): s
                for path, s in jax.tree_util.tree_flatten_with_path(sh)[0]}
        key_spec = next(v.spec for k, v in flat.items() if "cached_key" in k)
        assert "tensor" in tuple(key_spec)

    def test_decode_rejects_pipeline_parallel(self, devices8):
        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh
        from distributed_tensorflow_tpu.models.gpt2 import GPT2

        mesh = build_mesh(MeshConfig(data=4, pipe=2), devices8)
        _, cfg = _tiny_gpt2()
        model = GPT2(cfg, mesh=mesh)
        with pytest.raises(ValueError, match="pipe"):
            model.init(jax.random.key(0), jnp.zeros((4, 8), jnp.int32),
                       decode=True)


# ---------------------------------------------------------------------------
# ServeEngine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gpt2_engine(request):
    mesh_dp = request.getfixturevalue("mesh_dp")
    eng = ServeEngine("gpt2", mesh=mesh_dp, preset="tiny")
    yield eng
    eng.close()


class TestServeEngine:
    def test_pipe_mesh_rejected_at_construction(self, devices8):
        """A decode-capable model on a pipeline-split mesh must fail at
        ServeEngine CONSTRUCTION, naming the mesh axis — not deep inside
        the first decode apply after params already materialized."""
        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh

        mesh = build_mesh(MeshConfig(data=4, pipe=2), devices8)
        with pytest.raises(ValueError,
                           match=r"'pipe' axis of size 2.*pipeline"):
            ServeEngine("gpt2", mesh=mesh, preset="tiny")

    def test_generate_shape_dtype_determinism(self, gpt2_engine):
        vocab = gpt2_engine.module.cfg.vocab_size
        prompts = np.asarray(
            jax.random.randint(jax.random.key(4), (8, 6), 0, vocab))
        a = gpt2_engine.generate(prompts, max_new_tokens=5)
        b = gpt2_engine.generate(prompts, max_new_tokens=5)
        assert a.shape == (8, 5) and a.dtype == np.int32
        np.testing.assert_array_equal(a, b)  # greedy decode is deterministic
        assert (a >= 0).all() and (a < vocab).all()

    def test_generate_matches_full_forward_argmax(self, gpt2_engine):
        """The first generated token must equal argmax of the plain full
        forward — ties the serving path to the training-time model."""
        vocab = gpt2_engine.module.cfg.vocab_size
        prompts = np.asarray(
            jax.random.randint(jax.random.key(5), (8, 7), 0, vocab))
        gen = gpt2_engine.generate(prompts, max_new_tokens=1)
        logits = gpt2_engine.module.apply(
            {"params": gpt2_engine.params}, jnp.asarray(prompts))
        expect = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
        np.testing.assert_array_equal(gen[:, 0], expect)

    def test_generate_batch_pads_and_scatters(self, gpt2_engine):
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(0)
        # 3 ragged prompts of two lengths; batch dim padded internally.
        prompts = [rng.integers(0, vocab, size=(n,), dtype=np.int32)
                   for n in (6, 4, 6)]
        outs = gpt2_engine.generate_batch(prompts, max_new_tokens=3)
        assert [o.shape for o in outs] == [(3,)] * 3
        # Same-length prompts must agree with a direct padded generate.
        direct = gpt2_engine.generate(
            pad_rows(np.stack([prompts[0], prompts[2]]),
                     gpt2_engine.bucket_rows(2)), 3)
        np.testing.assert_array_equal(outs[0], direct[0])
        np.testing.assert_array_equal(outs[2], direct[1])

    def test_generate_rejects_overlong(self, gpt2_engine):
        n_pos = gpt2_engine.module.cfg.n_positions
        with pytest.raises(ValueError, match="n_positions"):
            gpt2_engine.generate(
                np.zeros((8, n_pos), np.int32), max_new_tokens=1)

    def test_bucket_rows_pow2_multiple_of_dp(self, gpt2_engine):
        dp = gpt2_engine.data_parallelism
        assert dp == 8
        assert gpt2_engine.bucket_rows(1) == 8
        assert gpt2_engine.bucket_rows(8) == 8
        assert gpt2_engine.bucket_rows(9) == 16

    def test_classify_mnist(self, mesh_dp):
        with ServeEngine("mnist", mesh=mesh_dp, batch_size=32) as eng:
            batch = next(eng.workload.data_fn(16))
            preds = eng.classify_batch(
                [{"image": batch["image"][i]} for i in range(10)])
        assert len(preds) == 10
        assert all(0 <= p < 10 for p in preds)

    def test_restore_roundtrip(self, mesh_dp, tmp_path):
        """Train-side save -> serve-side restore_params -> the saved
        params, each in the type the server holds it in (the float32 leaves
        the programs cast at every use rounded once, the layer norms as
        saved; tests/test_serve_weights.py), and a working generate — the
        checkpoint_dir acceptance path."""
        from distributed_tensorflow_tpu.checkpoint import CheckpointManager
        from distributed_tensorflow_tpu.models import get_workload
        from distributed_tensorflow_tpu.train_lib import build_state_and_step

        ckdir = str(tmp_path / "ck")
        wl = get_workload("gpt2", mesh=mesh_dp, preset="tiny")
        state, _, _, _ = build_state_and_step(wl, mesh_dp, total_steps=1)
        with CheckpointManager(ckdir, async_save=False) as m:
            assert m.save(0, state, force=True)
        saved_params = jax.device_get(state.params)

        with ServeEngine("gpt2", mesh=mesh_dp, checkpoint_dir=ckdir,
                         preset="tiny") as eng:
            assert eng.restored_step == 0
            served = jax.device_get(eng.params)
            jax.tree.map(
                lambda a, b: np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b).astype(a.dtype)),
                served, saved_params)
            assert served["wte"].dtype == jnp.bfloat16
            assert served["ln_f"]["scale"].dtype == np.float32
            out = eng.generate(np.zeros((8, 4), np.int32), 2)
        assert out.shape == (8, 2)

    def test_missing_checkpoint_falls_back_to_fresh_init(
            self, mesh_dp, tmp_path):
        with ServeEngine("gpt2", mesh=mesh_dp, preset="tiny",
                         checkpoint_dir=str(tmp_path / "empty")) as eng:
            assert eng.restored_step is None
            assert eng.generate(np.zeros((8, 4), np.int32), 1).shape == (8, 1)


# ---------------------------------------------------------------------------
# EOS early exit in the fixed-batch path
# ---------------------------------------------------------------------------

class TestEosEarlyExit:
    def _reference(self, eng, prompts, n):
        """Greedy stream with no eos — the early-exit runs must be a
        prefix of this (same jitted program, deterministic on CPU)."""
        return eng.generate(prompts, n)

    def test_stops_before_horizon_when_all_rows_hit_eos(self, gpt2_engine):
        vocab = gpt2_engine.module.cfg.vocab_size
        prompt = np.asarray(jax.random.randint(
            jax.random.key(8), (1, 6), 0, vocab))
        prompts = np.repeat(prompt, 8, axis=0)  # identical rows: one eos hit
        ref = self._reference(gpt2_engine, prompts, 12)
        eos = int(ref[0, 3])
        out = gpt2_engine.generate(prompts, 12, eos_token=eos,
                                   eos_check_every=1)
        assert out.shape[1] == 4  # stopped at the eos, not the horizon
        np.testing.assert_array_equal(out, ref[:, :4])

    def test_check_cadence_bounds_overshoot(self, gpt2_engine):
        """With eos_check_every=N the loop may overshoot by < N steps but
        still stops well short of the horizon; emitted tokens stay a prefix
        of the unrestricted stream."""
        vocab = gpt2_engine.module.cfg.vocab_size
        prompt = np.asarray(jax.random.randint(
            jax.random.key(8), (1, 6), 0, vocab))
        prompts = np.repeat(prompt, 8, axis=0)
        ref = self._reference(gpt2_engine, prompts, 16)
        eos = int(ref[0, 3])
        out = gpt2_engine.generate(prompts, 16, eos_token=eos,
                                   eos_check_every=4)
        assert 4 <= out.shape[1] < 4 + 4  # eos at 4, next check within 4
        np.testing.assert_array_equal(out, ref[:, : out.shape[1]])

    def test_no_eos_decodes_full_horizon(self, gpt2_engine):
        vocab = gpt2_engine.module.cfg.vocab_size
        prompts = np.asarray(jax.random.randint(
            jax.random.key(9), (8, 5), 0, vocab))
        ref = self._reference(gpt2_engine, prompts, 6)
        out = gpt2_engine.generate(prompts, 6, eos_token=vocab - 1
                                   if (ref != vocab - 1).all() else None,
                                   eos_check_every=1)
        np.testing.assert_array_equal(out, ref)

    def test_generate_batch_trims_each_row_at_its_eos(self, gpt2_engine):
        vocab = gpt2_engine.module.cfg.vocab_size
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, vocab, size=(5,), dtype=np.int32)
                   for _ in range(3)]
        ref = gpt2_engine.generate_batch(prompts, 8)
        eos = int(ref[1][2])  # row 1 should cut at index 2 (inclusive)
        outs = gpt2_engine.generate_batch(prompts, 8, eos_token=eos)
        assert len(outs[1]) <= 3 and outs[1][-1] == eos
        for r, o in zip(ref, outs):
            np.testing.assert_array_equal(o, r[: len(o)])


# ---------------------------------------------------------------------------
# CheckpointManager teardown surface (satellite b)
# ---------------------------------------------------------------------------

class TestCheckpointManagerClose:
    def test_close_idempotent_and_context_manager(self, tmp_path):
        from distributed_tensorflow_tpu.checkpoint import CheckpointManager

        m = CheckpointManager(str(tmp_path / "a"))
        assert not m.closed
        m.close()
        assert m.closed
        m.close()  # second close is a no-op
        m.wait_until_finished()  # safe after close

        with CheckpointManager(str(tmp_path / "b")) as m2:
            assert not m2.closed
        assert m2.closed

    def test_restore_params_without_template(self, tmp_path):
        import optax

        from distributed_tensorflow_tpu.checkpoint import CheckpointManager
        from distributed_tensorflow_tpu.training import TrainState

        params = {"w": jnp.arange(4.0), "b": jnp.ones((2,))}
        state = TrainState.create(
            apply_fn=lambda *a, **k: None, params=params,
            tx=optax.sgd(0.1), model_state={})
        d = str(tmp_path / "ck")
        with CheckpointManager(d, async_save=False) as m:
            m.save(3, state, force=True)
        with CheckpointManager(d) as m:
            got, model_state = m.restore_params()
        assert model_state == {}
        np.testing.assert_array_equal(np.asarray(got["w"]), np.arange(4.0))

    def test_restore_params_missing_dir_raises(self, tmp_path):
        from distributed_tensorflow_tpu.checkpoint import CheckpointManager

        with CheckpointManager(str(tmp_path / "none")) as m:
            with pytest.raises(FileNotFoundError):
                m.restore_params()


# ---------------------------------------------------------------------------
# ServeMonitorHook
# ---------------------------------------------------------------------------

class TestServeMonitorHook:
    def test_exports_batcher_counters(self, caplog):
        import logging

        from distributed_tensorflow_tpu.obs import ServeMonitorHook

        rec = _Recorder()
        with DynamicBatcher(rec, max_batch_size=2, batch_timeout_ms=2) as b:
            hook = ServeMonitorHook(b, every_steps=1)
            futs = [b.submit(i) for i in range(4)]
            for f in futs:
                f.result(timeout=5)
            m = hook.metrics()
            with caplog.at_level(logging.INFO,
                                 logger="distributed_tensorflow_tpu.obs.serve"):
                logged = hook.log(4)
        for key in ("serve_queue_depth", "serve_completed",
                    "serve_avg_batch_occupancy", "serve_p50_latency_ms",
                    "serve_p99_latency_ms", "serve_rejected"):
            assert key in m, m
        assert logged["serve_completed"] == 4.0
        assert any("serve @ 4" in r.message for r in caplog.records)

    def test_tolerates_source_without_stats(self):
        from distributed_tensorflow_tpu.obs import ServeMonitorHook

        hook = ServeMonitorHook(object())
        assert hook.metrics() == {}
        assert hook.log(1) is None
