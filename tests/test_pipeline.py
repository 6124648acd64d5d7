"""Pipeline-parallelism tests: the pipelined program must equal sequential
stage application (forward and backward) — the schedule is an execution
detail, not a semantic change.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh
from distributed_tensorflow_tpu.parallel.pipeline import (
    pipeline_apply,
    pipeline_value_and_grad,
    stack_stage_params,
    stage_sharding,
)


@pytest.fixture(scope="module")
def mesh_pp():
    return build_mesh(MeshConfig(data=2, pipe=4), jax.devices())


def stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def make_stages(n_stages=4, dim=8, seed=0):
    rng = np.random.RandomState(seed)
    return [
        {
            "w": jnp.asarray(rng.randn(dim, dim).astype(np.float32) * 0.5),
            "b": jnp.asarray(rng.randn(dim).astype(np.float32) * 0.1),
        }
        for _ in range(n_stages)
    ]


def sequential(stages, x):
    for p in stages:
        x = jax.vmap(lambda mb: stage_fn(p, mb))(x)
    return x


class TestPipeline:
    def test_matches_sequential(self, mesh_pp):
        stages = make_stages(4)
        stacked = stack_stage_params(stages)
        stacked = jax.device_put(stacked, stage_sharding(mesh_pp, stacked))
        x = jnp.asarray(
            np.random.RandomState(1).randn(8, 4, 8).astype(np.float32)
        )  # (M=8 microbatches, mb=4, dim=8)
        got = pipeline_apply(stage_fn, stacked, x, mesh=mesh_pp)
        want = sequential(stages, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    def test_gradients_match_sequential(self, mesh_pp):
        stages = make_stages(4)
        stacked = stack_stage_params(stages)
        stacked_sharded = jax.device_put(
            stacked, stage_sharding(mesh_pp, stacked)
        )
        x = jnp.asarray(
            np.random.RandomState(2).randn(8, 4, 8).astype(np.float32)
        )

        def loss_pp(p):
            return jnp.sum(pipeline_apply(stage_fn, p, x, mesh=mesh_pp) ** 2)

        def loss_seq(stages_list):
            return jnp.sum(sequential(stages_list, x) ** 2)

        g_pp = jax.jit(jax.grad(loss_pp))(stacked_sharded)
        g_seq = jax.grad(loss_seq)(stages)
        g_seq_stacked = stack_stage_params(g_seq)
        for a, b in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_seq_stacked)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_1f1b_matches_gpipe_and_sequential(self, mesh_pp):
        """The schedule is an execution detail: 1F1B's loss, param grads,
        and input cotangent must equal GPipe's and plain sequential
        autodiff's."""
        stages = make_stages(4)
        stacked = stack_stage_params(stages)
        stacked = jax.device_put(stacked, stage_sharding(mesh_pp, stacked))
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(8, 4, 8).astype(np.float32))
        tgt = jnp.asarray(rng.randn(8, 4, 8).astype(np.float32))

        def loss_fn(y, t):
            return jnp.mean((y - t) ** 2)

        def run(schedule):
            return jax.jit(lambda p, xx, t: pipeline_value_and_grad(
                stage_fn, loss_fn, p, xx, t, mesh=mesh_pp,
                schedule=schedule))(stacked, x, tgt)

        l_1f1b, g_1f1b, dx_1f1b, _ = run("1f1b")
        l_gp, g_gp, dx_gp, _ = run("gpipe")

        def loss_seq(stages_list, xx):
            y = sequential(stages_list, xx)
            return jnp.mean(jax.vmap(loss_fn)(y, tgt))

        l_seq, (g_seq, dx_seq) = jax.value_and_grad(
            loss_seq, argnums=(0, 1)
        )(stages, x)
        g_seq = stack_stage_params(g_seq)

        np.testing.assert_allclose(float(l_1f1b), float(l_seq), rtol=1e-5)
        np.testing.assert_allclose(float(l_gp), float(l_seq), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(g_1f1b), jax.tree.leaves(g_seq)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
        for a, b in zip(jax.tree.leaves(g_1f1b), jax.tree.leaves(g_gp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dx_1f1b), np.asarray(dx_seq),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dx_gp), np.asarray(dx_seq),
                                   rtol=1e-4, atol=1e-5)

    def test_1f1b_full_model_with_embedding_and_tied_head(self, mesh_pp):
        """The deep-pipe composition recipe (PipelineVJP docstring): an
        embedding feeds the pipeline, a trainable TIED head consumes it;
        1F1B grads (stage + tail + embedding-through-dx, with the tied
        table summing both paths) must equal plain autodiff of the
        sequential model."""
        V, d, M, mb, Tt = 32, 8, 8, 4, 6
        rng = np.random.RandomState(7)
        E = jnp.asarray(rng.randn(V, d).astype(np.float32) * 0.3)
        stages = make_stages(4, dim=d)
        stacked = stack_stage_params(stages)
        stacked = jax.device_put(stacked, stage_sharding(mesh_pp, stacked))
        tokens = jnp.asarray(rng.randint(0, V, size=(M, mb, Tt)))
        tgt_tok = jnp.asarray(rng.randint(0, V, size=(M, mb, Tt)))

        def embed_fn(E, tokens):
            return E[tokens]  # (M, mb, T, d)

        def head_loss(tp, y_mb, tgt_mb):
            logits = y_mb @ tp["E"].T  # tied head
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(
                jnp.take_along_axis(logp, tgt_mb[..., None], axis=-1)
            )

        def run(schedule):
            x, emb_vjp = jax.vjp(embed_fn, E, tokens)
            r = pipeline_value_and_grad(
                stage_fn, None, stacked, x, tgt_tok, mesh=mesh_pp,
                schedule=schedule, tail_fn=head_loss,
                tail_params={"E": E},
            )
            dE_emb, _ = emb_vjp(r.dx)
            return r.loss, r.grads, dE_emb + r.tail_grads["E"]

        # plain autodiff reference on the unrolled model
        def ref_loss(E, stages_list):
            x = embed_fn(E, tokens)

            def per_mb(xm, tm):
                h = xm
                for p in stages_list:
                    h = stage_fn(p, h)
                return head_loss({"E": E}, h, tm)

            return jnp.mean(jax.vmap(per_mb)(x, tgt_tok))

        l_ref, (dE_ref, dstages_ref) = jax.value_and_grad(
            ref_loss, argnums=(0, 1)
        )(E, stages)
        dstages_ref = stack_stage_params(dstages_ref)

        for schedule in ("1f1b", "gpipe"):
            loss, grads, dE = jax.jit(run, static_argnums=0)(schedule)
            np.testing.assert_allclose(float(loss), float(l_ref), rtol=1e-5)
            np.testing.assert_allclose(np.asarray(dE), np.asarray(dE_ref),
                                       rtol=1e-4, atol=1e-5)
            for a, b in zip(jax.tree.leaves(grads),
                            jax.tree.leaves(dstages_ref)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("n_stages", [2, 4])
    def test_1f1b_bounded_stash_memory(self, n_stages):
        """1F1B's live set is the depth-(2S-1) input ring, not GPipe's
        O(M) tick stash: compiled temp memory at M=16 must be strictly
        smaller, at pipe=2 AND at the deeper pipe=4 (the config class the
        schedule exists for)."""
        mesh = build_mesh(MeshConfig(pipe=n_stages),
                          jax.devices()[:n_stages])
        # Activation-dominated shapes (big microbatch, small params): the
        # schedules differ in activation stashing, not in the param-grad
        # accumulators both must hold.
        dim, M, mb = 64, 16, 128
        stages = make_stages(n_stages, dim=dim)
        stacked = stack_stage_params(stages)
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(M, mb, dim).astype(np.float32))
        tgt = jnp.asarray(rng.randn(M, mb, dim).astype(np.float32))

        def loss_fn(y, t):
            return jnp.mean((y - t) ** 2)

        def run(schedule):
            return jax.jit(
                lambda p: pipeline_value_and_grad(
                    stage_fn, loss_fn, p, x, tgt, mesh=mesh,
                    schedule=schedule,
                )
            )

        temps = {}
        for schedule in ("1f1b", "gpipe"):
            mem = run(schedule).lower(stacked).compile().memory_analysis()
            if mem is None or not hasattr(mem, "temp_size_in_bytes"):
                pytest.skip("backend exposes no memory analysis")
            temps[schedule] = mem.temp_size_in_bytes
        assert temps["1f1b"] < temps["gpipe"], temps

    def test_single_stage_mesh_falls_back(self, mesh_dp):
        stages = make_stages(1)
        stacked = stack_stage_params(stages)
        x = jnp.asarray(
            np.random.RandomState(3).randn(4, 2, 8).astype(np.float32)
        )
        got = pipeline_apply(stage_fn, stacked, x, mesh=mesh_dp, axis="pipe")
        want = sequential(stages, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
@pytest.mark.parametrize("inner", ["data", "tensor"])
def test_flash_kernel_inside_pipeline_stages(monkeypatch, inner, schedule):
    """The stages run manual over ``pipe`` only; the flash kernel nests its
    own shard_map over the remaining axes (a Mosaic kernel cannot be left to
    the partitioner, not even over size-1 auto axes).  With batch rows or
    heads split inside the stage, the losses must equal the dense-attention
    pipeline's.  (Four devices: 1F1B over data x tensor x pipe, dense or
    not, aborts in XLA:CPU's SPMD partitioner — see ROADMAP.)"""
    from distributed_tensorflow_tpu.models import get_workload
    from distributed_tensorflow_tpu.models.gpt2 import GPT2Config
    from tests.helpers import stream_fed_losses

    monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
    mesh = build_mesh(MeshConfig(**{"data": 1, "pipe": 2, inner: 2}),
                      jax.devices()[:4])

    def losses(flash):
        wl = get_workload(
            "gpt2", config=GPT2Config.tiny(), batch_size=16, seq_len=128,
            grad_accum_steps=1, mesh=mesh, pipe_schedule=schedule,
            use_flash_attention=flash)  # 8 microbatches of 2 rows
        return stream_fed_losses(wl, mesh)

    # bf16 activations: the kernel and XLA round the softmax differently
    np.testing.assert_allclose(losses(True), losses(False), rtol=1e-4)
