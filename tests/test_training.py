"""End-to-end training-slice tests: step, loop, checkpoint, train_lib.

Mirrors SURVEY.md §5's tier (a)/(b): unit + simulated-mesh tests.  The
acceptance bar for the slice is the reference's own: loss goes down on the
MNIST workload, checkpoints resume exactly, hooks observe what they should.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_tensorflow_tpu.checkpoint import CheckpointManager
from distributed_tensorflow_tpu.models import available_models, get_workload
from distributed_tensorflow_tpu.train_lib import TrainArgs, build_state_and_step, run
from distributed_tensorflow_tpu.training import (
    BF16,
    FP32,
    LoggingHook,
    NanHook,
    TrainLoop,
    TrainState,
    make_train_step,
)


def quadratic_loss(params, batch, rng):
    pred = batch["x"] @ params["w"] + params["b"]
    loss = jnp.mean((pred - batch["y"]) ** 2)
    return loss, {"mae": jnp.mean(jnp.abs(pred - batch["y"]))}


def make_linear_state(lr=0.1):
    params = {"w": jnp.zeros((4, 1)), "b": jnp.zeros((1,))}
    return TrainState.create(
        apply_fn=lambda p, x: x @ p["w"] + p["b"],
        params=params,
        tx=optax.sgd(lr),
    )


def linear_batch(n=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4).astype(np.float32)
    w = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
    y = x @ w + 0.1
    return {"x": x, "y": y}


class TestTrainStep:
    def test_linear_regression_converges(self):
        state = make_linear_state()
        step = make_train_step(quadratic_loss, precision=FP32)
        batch = linear_batch()
        rng = jax.random.key(0)
        losses = []
        for _ in range(100):
            state, m = step(state, batch, rng)
            losses.append(float(m["loss"]))
        assert losses[-1] < 0.02 * losses[0]

    def test_grad_accum_matches_full_batch(self):
        # SGD: mean-of-microbatch-grads == full-batch grad, so one accum step
        # must equal one full-batch step exactly (up to fp assoc).
        batch = linear_batch(64)
        rng = jax.random.key(0)

        s_full = make_linear_state()
        step_full = make_train_step(quadratic_loss, precision=FP32)
        s_full, m_full = step_full(s_full, batch, rng)

        s_acc = make_linear_state()
        step_acc = make_train_step(
            quadratic_loss, grad_accum_steps=4, precision=FP32
        )
        s_acc, m_acc = step_acc(s_acc, batch, rng)

        np.testing.assert_allclose(
            np.asarray(s_full.params["w"]), np.asarray(s_acc.params["w"]),
            rtol=1e-5,
        )
        assert int(s_acc.step) == 1

    def test_clip_grad_norm(self):
        state = make_linear_state(lr=1.0)
        w_before = np.asarray(state.params["w"]).copy()  # state is donated
        step = make_train_step(
            quadratic_loss, precision=FP32, clip_grad_norm=1e-3
        )
        batch = linear_batch()
        new_state, m = step(state, batch, jax.random.key(0))
        delta = jnp.linalg.norm(np.asarray(new_state.params["w"]) - w_before)
        assert float(delta) <= 1.1e-3
        assert "grad_norm" in m

    def test_bf16_policy_keeps_master_f32(self):
        state = make_linear_state()
        step = make_train_step(quadratic_loss, precision=BF16)
        state, _ = step(state, linear_batch(), jax.random.key(0))
        assert state.params["w"].dtype == jnp.float32


class TestEval:
    def test_periodic_eval_in_training(self):
        from distributed_tensorflow_tpu.train_lib import TrainArgs, run

        result = run(TrainArgs(
            model="mnist", steps=20, batch_size=32, log_every=10,
            eval_every=10, eval_batches=2,
        ))
        assert result["final_step"] == 20
        assert "eval_loss" in result
        assert np.isfinite(result["eval_loss"])

    def test_checkpoint_knobs_flow_from_flags(self, tmp_path):
        """--max_to_keep / --sync_checkpoint reach the manager (VERDICT r4
        weak #7: train_lib hard-coded max_to_keep=3)."""
        from distributed_tensorflow_tpu.train_lib import (
            TrainArgs,
            parse_args,
            run,
        )

        args = parse_args([
            "--model=mnist", "--steps=10", "--batch_size=32",
            "--checkpoint_every=2", "--max_to_keep=1", "--sync_checkpoint",
            f"--checkpoint_dir={tmp_path / 'ckpt'}",
        ])
        assert args.max_to_keep == 1 and args.sync_checkpoint
        run(args)
        from distributed_tensorflow_tpu.checkpoint import CheckpointManager

        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        try:
            assert mgr.all_steps() == [10]  # retained exactly max_to_keep
        finally:
            mgr.close()

    def test_table_dtype_flag_parses_and_validates(self):
        from distributed_tensorflow_tpu.train_lib import parse_args

        args = parse_args(["--model=wide_deep", "--table_dtype=bf16"])
        assert args.table_dtype == "bf16"
        import pytest

        from distributed_tensorflow_tpu.train_lib import TrainArgs, run

        with pytest.raises(ValueError, match="table_dtype"):
            run(TrainArgs(model="mnist", table_dtype="bf16", steps=1))

    def test_evaluator_role_consumes_checkpoints(self, tmp_path):
        from distributed_tensorflow_tpu.train_lib import (
            TrainArgs,
            run,
            run_evaluator,
        )

        ckpt = str(tmp_path / "ckpt")
        run(TrainArgs(
            model="mnist", steps=10, batch_size=32, log_every=5,
            checkpoint_dir=ckpt, checkpoint_every=5,
        ))
        result = run_evaluator(TrainArgs(
            model="mnist", steps=10, batch_size=32, checkpoint_dir=ckpt,
            eval_batches=2,
        ))
        assert result["final_step"] == 10
        assert "eval_loss" in result and np.isfinite(result["eval_loss"])


class TestTrainLoop:
    def test_loop_runs_hooks_and_counts_steps(self, caplog):
        state = make_linear_state()
        step = make_train_step(quadratic_loss, precision=FP32)
        data = iter(lambda: linear_batch(), None)  # infinite same batch

        loop = TrainLoop(
            step, state, data,
            hooks=[LoggingHook(every_steps=10), NanHook()],
            examples_per_step=64, metrics_every=5,
        )
        with caplog.at_level(logging.INFO):
            final = loop.run(20)
        assert int(jax.device_get(final.step)) == 20
        assert loop.last_logged_metrics.get("loss") is not None

    def test_nan_hook_raises(self):
        def bad_loss(params, batch, rng):
            return jnp.float32(jnp.nan), {}

        state = make_linear_state()
        step = make_train_step(bad_loss, precision=FP32)
        data = iter(lambda: linear_batch(), None)
        loop = TrainLoop(step, state, data, hooks=[NanHook()],
                         metrics_every=1)
        with pytest.raises(FloatingPointError):
            loop.run(3)


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        state = make_linear_state()
        step = make_train_step(quadratic_loss, precision=FP32)
        state, _ = step(state, linear_batch(), jax.random.key(0))

        mngr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
        assert mngr.save(1, state)
        mngr.wait_until_finished()
        assert mngr.latest_step() == 1

        fresh = make_linear_state()
        restored = mngr.restore(template=fresh)
        np.testing.assert_allclose(
            np.asarray(restored.params["w"]), np.asarray(state.params["w"])
        )
        assert int(restored.step) == 1
        mngr.close()

    def test_restore_or_init_without_checkpoint(self, tmp_path):
        mngr = CheckpointManager(str(tmp_path / "empty"), async_save=False)
        state = make_linear_state()
        out = mngr.restore_or_init(state)
        assert out is state
        mngr.close()

    def test_max_to_keep(self, tmp_path):
        mngr = CheckpointManager(
            str(tmp_path / "gc"), max_to_keep=2, async_save=False
        )
        state = make_linear_state()
        for s in (1, 2, 3):
            mngr.save(s, state, force=True)
        mngr.wait_until_finished()
        assert list(mngr.all_steps()) == [2, 3]
        mngr.close()


def _tiny_step(axes, devices, *, site, accum=4, rows=16, dropout=0.0,
               model="gpt2"):
    """Tiny GPT-2 (or BERT) in f32 on a mesh of ``axes``: ``(init, step,
    batch)``.  ``site`` picks who reduces the gradients over ``data``:
    ``"after_scan"`` is ``build_step``'s own step, ``"in_scan"`` the same
    loss accumulated with no mesh told to the step, so that GSPMD reduces
    every microbatch's gradient where it is made (the step before
    deferral)."""
    import dataclasses

    from distributed_tensorflow_tpu import train_lib
    from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh
    from distributed_tensorflow_tpu.models.bert import BertConfig
    from distributed_tensorflow_tpu.models.gpt2 import GPT2Config
    from distributed_tensorflow_tpu.training import shard_train_step

    mesh = build_mesh(MeshConfig(**axes), devices[:int(np.prod(list(
        axes.values()) or [1]))])
    cfg = dataclasses.replace(
        {"gpt2": GPT2Config, "bert": BertConfig}[model].tiny(),
        dropout=dropout, dtype=jnp.float32)
    # SGD, so that the parameters' change IS the (clipped) gradient: Adam's
    # first update is lr * sign(g), which hides a wrong scale and amplifies
    # the rounding of a gradient near 0.
    workload = dataclasses.replace(
        get_workload(model, mesh=mesh, config=cfg, batch_size=rows,
                     seq_len=32, grad_accum_steps=accum),
        make_optimizer=optax.sgd)
    init, _, shardings, step, batch_sh = train_lib.build_step(
        workload, mesh, precision=FP32, grad_accum_steps=accum,
        learning_rate=0.5, total_steps=10)
    if site == "in_scan":
        raw = make_train_step(
            workload.loss_fn, grad_accum_steps=accum, precision=FP32,
            clip_grad_norm=workload.clip_grad_norm, jit=False,
            in_step_rng=True)
        step = shard_train_step(
            raw, mesh, shardings, batch_sh[workload.example_key])
    if model == "gpt2":
        batch = {"tokens": np.random.RandomState(0).randint(
            0, cfg.vocab_size, (rows, 32)).astype(np.int32)}
    else:
        batch = next(workload.data_fn(rows))
    return init, step, jax.device_put(batch, batch_sh)


def _gpt2_loss(dropout):
    """The tiny f32 GPT-2's training loss with no mesh: what one data
    replica computes."""
    import dataclasses

    from distributed_tensorflow_tpu.models.gpt2 import GPT2Config

    cfg = dataclasses.replace(
        GPT2Config.tiny(), dropout=dropout, dtype=jnp.float32)
    return get_workload("gpt2", config=cfg, batch_size=8, seq_len=32,
                        grad_accum_steps=4).loss_fn


def _two_steps(init, step, batch):
    """Loss and gradient norm of the first step (learning rate 0 in the
    warm-up's first step) and the parameters after the second."""
    state, first = step(init(), batch, jax.random.key(1))
    state, _ = step(state, batch, jax.random.key(1))
    return jax.device_get((first["loss"], first["grad_norm"], state.params))


class TestDeferredGradReduce:
    """Each data replica sums its own microbatches' gradients and the step
    reduces the f32 accumulator over ``data`` once, after the scan."""

    @pytest.mark.parametrize(
        "axes",
        [{"data": 2, "tensor": 2}, {"data": 4}, {"data": 2, "fsdp": 2}],
        ids=["data2xtensor2", "data4", "data2xfsdp2"])
    @pytest.mark.parametrize("against", ["in_scan", "one_device"])
    def test_matches_the_step_it_replaces(self, devices8, axes, against):
        """Same rows, same loss, same gradient up to the order of an f32
        sum: against GSPMD's in-loop reduction on the same mesh, and
        against one device scanning the same rows."""
        init, step, batch = _tiny_step(axes, devices8, site="after_scan")
        assert step.grad_reduce == "after_scan"
        ref_axes, ref_site = ((axes, "in_scan") if against == "in_scan"
                              else ({}, "after_scan"))
        r_init, r_step, r_batch = _tiny_step(
            ref_axes, devices8, site=ref_site)
        assert r_step.grad_reduce in ("in_scan", "none")
        loss, gnorm, params = _two_steps(init, step, batch)
        r_loss, r_gnorm, r_params = _two_steps(r_init, r_step, r_batch)
        np.testing.assert_allclose(loss, r_loss, rtol=1e-5)
        np.testing.assert_allclose(gnorm, r_gnorm, rtol=1e-4)
        moved = jax.tree.map(lambda a, b: float(np.abs(a - b).max()),
                             jax.device_get(init().params), params)
        assert max(jax.tree.leaves(moved)) > 1e-3   # the update is not 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(r_params)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)

    def test_bert_mean_over_masked_positions_keeps_its_contract(
            self, devices8):
        """BERT's loss is a mean over a varying count of masked positions:
        accumulation already made it a mean of microbatch means, and it is
        now one of per-replica microbatch means.  Same kind, close value."""
        axes = {"data": 2, "tensor": 2}
        init, step, batch = _tiny_step(
            axes, devices8, site="after_scan", accum=2, model="bert")
        assert step.grad_reduce == "after_scan"
        r_init, r_step, r_batch = _tiny_step(
            axes, devices8, site="in_scan", accum=2, model="bert")
        loss, gnorm, _ = _two_steps(init, step, batch)
        r_loss, r_gnorm, _ = _two_steps(r_init, r_step, r_batch)
        np.testing.assert_allclose(loss, r_loss, rtol=2e-2)
        np.testing.assert_allclose(gnorm, r_gnorm, rtol=5e-2)

    @pytest.mark.parametrize("data", [2, 4])
    def test_replicas_and_microbatches_draw_their_own_rng(self, devices8,
                                                          data):
        """What a dropout mask is drawn from: the key each replica's loss
        gets in each microbatch.  The loss runs inside the map, so it can
        say which replica it is."""
        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh
        from distributed_tensorflow_tpu.parallel.sharding import (
            batch_sharding, replicated)
        from distributed_tensorflow_tpu.training import shard_train_step

        mesh = build_mesh(MeshConfig(data=data), devices8[:data])
        accum = 4

        def loss_fn(params, batch, rng):
            here = jax.nn.one_hot(jax.lax.axis_index("data"), data)
            draw = jax.random.uniform(rng)
            return jnp.sum(params["w"]) * 0.0, {"draw": here * draw}

        state = TrainState.create(
            apply_fn=None, params={"w": jnp.zeros((4,))}, tx=optax.sgd(0.1))
        raw = make_train_step(loss_fn, grad_accum_steps=accum, precision=FP32,
                              jit=False, mesh=mesh)
        assert raw.grad_reduce == "after_scan"
        # Keep the microbatches apart: aux comes back averaged over them,
        # so one call per accumulation depth shows each one's share.
        step = shard_train_step(
            raw, mesh, jax.tree.map(lambda _: replicated(mesh), state),
            batch_sharding(mesh))
        batch = {"x": np.zeros((data * accum * 2, 4), np.float32)}
        _, metrics = step(state, batch, jax.random.key(0))
        draws = np.asarray(metrics["draw"]) * data   # undo the pmean
        assert len(set(np.round(draws, 6))) == data, draws

        def micro_draws(rng):
            """One replica's draws, as the step derives them."""
            keys = [jax.random.fold_in(rng, i) for i in range(accum)]
            return [float(jax.random.uniform(k)) for k in keys]

        for replica in range(data):
            rng = jax.random.fold_in(jax.random.key(0), replica)
            want = micro_draws(rng)
            assert len(set(np.round(want, 6))) == accum
            np.testing.assert_allclose(draws[replica], np.mean(want),
                                       rtol=1e-5)

    def test_dropout_masks_differ_between_replicas(self, devices8):
        """Tiny GPT-2 with dropout on ``data=2``, both replicas given the
        same rows: the step's loss is the mean of what one device reads
        from those rows under replica 0's key and under replica 1's, which
        differ (with one mask for both it would equal either)."""
        rows = 8
        init, step, batch = _tiny_step(
            {"data": 2}, devices8, site="after_scan", rows=2 * rows,
            dropout=0.3)
        half = np.asarray(batch["tokens"])[:rows]
        batch = {"tokens": jax.device_put(
            np.concatenate([half, half]), batch["tokens"].sharding)}
        base = jax.random.key(1)
        _, metrics = step(init(), batch, base)

        one_init, _, _ = _tiny_step(
            {}, devices8, site="after_scan", rows=rows, dropout=0.3)
        # One device, told no mesh and handed the key itself: the step
        # count folded in, then the replica's index.
        one_step = make_train_step(
            _gpt2_loss(0.3), grad_accum_steps=4, precision=FP32,
            clip_grad_norm=1.0, donate=False)
        this_step = jax.random.fold_in(base, jnp.uint32(0))
        alone = [
            float(one_step(one_init(), {"tokens": half},
                           jax.random.fold_in(this_step, replica))[1]["loss"])
            for replica in range(2)]
        assert abs(alone[0] - alone[1]) > 1e-4
        np.testing.assert_allclose(float(metrics["loss"]), np.mean(alone),
                                   rtol=1e-5)

    @pytest.mark.parametrize("axes,accum,word", [
        ({"data": 1}, 4, "none"),
        ({"data": 2}, 1, "in_scan"),
        ({"data": 1, "tensor": 2}, 4, "none"),
    ], ids=["data1", "accum1", "tensor-only"])
    def test_nothing_to_defer_is_the_step_it_was(self, devices8, axes, accum,
                                                 word):
        """No ``data`` axis or no accumulation: the step says so and traces
        to the program a step that was told no mesh traces to."""
        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh

        mesh = build_mesh(MeshConfig(**axes),
                          devices8[:int(np.prod(list(axes.values())))])
        kw = dict(grad_accum_steps=accum, precision=FP32, jit=False,
                  clip_grad_norm=1.0)
        with_mesh = make_train_step(quadratic_loss, mesh=mesh, **kw)
        without = make_train_step(quadratic_loss, **kw)
        assert with_mesh.grad_reduce == word
        assert without.grad_reduce == "none"
        args = (make_linear_state(), linear_batch(), jax.random.key(0))
        assert str(jax.make_jaxpr(with_mesh)(*args)) \
            == str(jax.make_jaxpr(without)(*args))

    @pytest.mark.parametrize("axes,kw,word", [
        ({"data": 2}, {}, "after_scan"),
        ({"data": 2, "fsdp": 2, "tensor": 2}, {}, "after_scan"),
        ({"data": 2}, {"batch_rows": 16}, "after_scan"),
        ({"data": 2}, {"batch_rows": 12}, "in_scan"),
        ({"data": 2}, {"stateful": True}, "in_scan"),
        ({"data": 2, "pipe": 2}, {}, "in_scan"),
        ({"data": 2, "context": 2}, {}, "in_scan"),
        ({"data": 2, "expert": 2}, {}, "in_scan"),
        ({"data": 2}, {"state_over": ("data",)}, "in_scan"),
        ({"data": 2, "tensor": 2}, {"state_over": ("tensor",)}, "after_scan"),
        ({"data": 2, "fsdp": 2}, {"state_over": (("data", "fsdp"),)},
         "in_scan"),
    ], ids=["data", "data-fsdp-tensor", "rows-divide", "rows-do-not-divide",
            "stateful", "pipe", "context",
            "expert", "state-over-data", "state-over-tensor",
            "state-over-data-and-fsdp"])
    def test_where_the_reduction_sits(self, devices8, axes, kw, word):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh
        from distributed_tensorflow_tpu.training import grad_reduce_site

        mesh = build_mesh(MeshConfig(**axes),
                          devices8[:int(np.prod(list(axes.values())))])
        kw = dict(kw)
        over = kw.pop("state_over", None)
        shardings = {"w": NamedSharding(mesh, P(*over)) if over else
                     NamedSharding(mesh, P())}
        assert grad_reduce_site(mesh, 4, state_shardings=shardings,
                                **kw) == word

    def test_stateful_workload_keeps_global_batch_statistics(self, devices8):
        """``build_step`` for a model with batch statistics on a ``data``
        mesh: not deferred, and the step says so."""
        from distributed_tensorflow_tpu import train_lib
        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh

        mesh = build_mesh(MeshConfig(data=2), devices8[:2])
        workload = get_workload("resnet50", batch_size=8, num_classes=10,
                                image_size=32, stage_sizes=(1, 1, 1, 1))
        assert workload.stateful
        step = train_lib.build_step(workload, mesh, grad_accum_steps=2)[3]
        assert step.grad_reduce == "in_scan"

    @pytest.mark.parametrize("cell,word", [
        ("train.gpt2-large.d2t2", "after_scan"),
        ("train.gpt2-medium.seq1024", "none"),
    ])
    def test_the_benchmarks_training_cells(self, devices8, cell, word):
        """The two training cells' own steps, built from shapes alone at
        their published widths: the four-chip one defers, the one-chip
        control has nothing to defer over."""
        from benchmark.harness import spec, train

        cell = spec.load_cell(cell)
        step = train.build_step(cell, devices8[:cell.chips])[4]
        assert step.grad_reduce == word

    @pytest.mark.parametrize("axes,accum,word", [
        ({"data": 2, "tensor": 2}, 4, "after_scan"),
        ({"data": 1}, 4, "none"),
    ], ids=["cell4-mesh", "cell1-mesh"])
    def test_build_step_records_the_choice(self, devices8, axes, accum, word):
        """One ``dtt/startup/build_step`` span, in the repo's tracer, whose
        arguments say where the step sums gradients; recorded with the
        tracer off (the category always is)."""
        from distributed_tensorflow_tpu.obs.trace import default_tracer

        tracer = default_tracer()
        assert not tracer.enabled
        before = len(tracer.spans(name="dtt/startup/build_step"))
        _, step, _ = _tiny_step(axes, devices8, site="after_scan",
                                accum=accum)
        assert step.grad_reduce == word
        (mark,) = tracer.spans(name="dtt/startup/build_step")[before:]
        args = mark[4]
        assert {k: args[k] for k in ("grad_reduce", "data", "accum")} == {
            "grad_reduce": word, "data": axes["data"], "accum": accum}


class TestTrainLib:
    def test_mnist_end_to_end_loss_decreases(self, tmp_path):
        res = run(TrainArgs(
            model="mnist", steps=150, batch_size=64, log_every=50,
            learning_rate=3e-3, precision="fp32",
        ))
        assert res["final_step"] == 150
        assert res["loss"] < 2.0  # clearly better than uniform 10-class CE

    def test_mnist_sharded_over_mesh_axes(self):
        # data x fsdp mesh exercise on the virtual 8-device mesh.
        res = run(TrainArgs(
            model="mnist", steps=20, batch_size=64, data=4, fsdp=2,
            log_every=10, precision="fp32",
        ))
        assert res["final_step"] == 20

    def test_checkpoint_resume_continues_at_step(self, tmp_path):
        ckpt = str(tmp_path / "resume")
        run(TrainArgs(model="mnist", steps=30, batch_size=64,
                      checkpoint_dir=ckpt, checkpoint_every=10,
                      log_every=10, precision="fp32"))
        res = run(TrainArgs(model="mnist", steps=50, batch_size=64,
                            checkpoint_dir=ckpt, checkpoint_every=10,
                            log_every=10, precision="fp32"))
        assert res["final_step"] == 50

    def test_ps_task_parks_and_returns_nothing(self):
        import threading

        from distributed_tensorflow_tpu.cluster import server as server_mod

        # Run ps-role entrypoint in a thread; it parks in join().  We can't
        # easily shut it down through run()'s internals, so assert it is
        # still parked after a moment, then release it via the Server object.
        import json, os
        env_backup = os.environ.get("TF_CONFIG")
        os.environ["TF_CONFIG"] = json.dumps({
            "cluster": {"worker": ["localhost:1"], "ps": ["localhost:2"]},
            "task": {"type": "ps", "index": 0},
        })
        try:
            result = {}
            t = threading.Thread(
                target=lambda: result.update(run(TrainArgs(model="mnist"))),
                daemon=True,
            )
            t.start()
            t.join(timeout=1.0)
            assert t.is_alive()  # parked, as a TF ps would be
        finally:
            if env_backup is None:
                del os.environ["TF_CONFIG"]
            else:
                os.environ["TF_CONFIG"] = env_backup


class TestWorkloadRegistry:
    def test_mnist_registered(self):
        assert "mnist" in available_models()
        w = get_workload("mnist", batch_size=32)
        assert w.batch_size == 32

    def test_unknown_model_raises(self):
        with pytest.raises(ValueError):
            get_workload("alexnet")
