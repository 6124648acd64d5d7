"""Sharded-embedding tests: the PS-replacement path (SURVEY.md §4.4).

Correctness bar: the shard_map exchange program must equal a plain dense
gather — forward AND backward — and never materialize the full table on one
device (structural property of the program; asserted via shard shapes).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.models import get_workload
from distributed_tensorflow_tpu.parallel.embedding import (
    ShardedEmbed,
    pad_vocab,
    replicated_lookup,
    sharded_lookup,
)


@pytest.fixture
def table_and_ids(mesh_dp):
    rng = np.random.RandomState(0)
    vocab, dim = 64, 8  # 64 rows over 8 shards = 8 rows/shard
    table = jnp.asarray(rng.randn(vocab, dim).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, vocab, size=(16, 4)).astype(np.int32))
    table = jax.device_put(table, NamedSharding(mesh_dp, P("data")))
    ids = jax.device_put(ids, NamedSharding(mesh_dp, P("data")))
    return table, ids


class TestShardedLookup:
    def test_matches_dense_gather(self, mesh_dp, table_and_ids):
        table, ids = table_and_ids
        got = sharded_lookup(table, ids, mesh=mesh_dp, axis="data")
        want = jnp.take(table, ids, axis=0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)

    def test_gradient_matches_dense(self, mesh_dp, table_and_ids):
        table, ids = table_and_ids
        w = jnp.arange(16 * 4 * 8, dtype=jnp.float32).reshape(16, 4, 8)

        def loss_sharded(t):
            return jnp.sum(sharded_lookup(t, ids, mesh=mesh_dp) * w)

        def loss_dense(t):
            return jnp.sum(jnp.take(t, ids, axis=0) * w)

        g_sharded = jax.grad(loss_sharded)(table)
        g_dense = jax.grad(loss_dense)(table)
        np.testing.assert_allclose(
            np.asarray(g_sharded), np.asarray(g_dense), rtol=1e-5
        )

    def test_table_stays_sharded(self, mesh_dp, table_and_ids):
        table, ids = table_and_ids
        out = jax.jit(
            lambda t, i: sharded_lookup(t, i, mesh=mesh_dp)
        )(table, ids)
        # output is batch-sharded, not replicated
        assert not out.sharding.is_fully_replicated
        # each table shard holds only vocab/8 rows
        shard_rows = {s.data.shape[0] for s in table.addressable_shards}
        assert shard_rows == {8}

    def test_pad_vocab(self):
        assert pad_vocab(100, 8) == 104
        assert pad_vocab(64, 8) == 64
        assert pad_vocab(1, 8) == 8

    def test_single_device_fallback(self):
        rng = np.random.RandomState(1)
        table = jnp.asarray(rng.randn(16, 4).astype(np.float32))
        ids = jnp.asarray([[0, 3], [5, 15]], dtype=jnp.int32)
        emb = ShardedEmbed(16, 4, mesh=None)
        vars_ = emb.init(jax.random.key(0), ids)
        out = emb.apply(vars_, ids)
        assert out.shape == (2, 2, 4)


class TestReplicatedLookup:
    """psum_sparse's caller: replicated small tables whose backward
    all-reduces sparse (ids, values) grads into dense form (TF's
    all_reduce_indexed_slices role, cross_device_utils.py:516)."""

    def test_matches_dense_fwd_and_grad(self, mesh_dp):
        rng = np.random.RandomState(2)
        table = jnp.asarray(rng.randn(24, 8).astype(np.float32))
        ids = jnp.asarray(rng.randint(0, 24, size=(16, 3)).astype(np.int32))
        w = jnp.asarray(rng.randn(16, 3, 8).astype(np.float32))

        def loss_rep(t):
            return jnp.sum(
                replicated_lookup(t, ids, mesh=mesh_dp,
                                  batch_axes=("data",)) * w)

        def loss_dense(t):
            return jnp.sum(jnp.take(t, ids, axis=0) * w)

        l1, g1 = jax.jit(jax.value_and_grad(loss_rep))(table)
        l2, g2 = jax.value_and_grad(loss_dense)(table)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5)

    def test_wide_deep_replicated_wide_parity(self, mesh_dp):
        """Same batch, same params: replicate_wide (psum_sparse backward)
        must produce the SAME loss and gradients as the sharded wide
        table.  vocab % 8 == 0 keeps the two layouts shape-identical."""
        from distributed_tensorflow_tpu.models.wide_deep import (
            WideDeep, _loss_fn,
        )

        rng = np.random.RandomState(3)
        batch = {
            "dense": jnp.asarray(rng.randn(16, 4).astype(np.float32)),
            "sparse": jnp.asarray(
                rng.randint(0, 64, size=(16, 5)).astype(np.int32)),
            "label": jnp.asarray(
                (rng.rand(16) > 0.5).astype(np.float32)),
        }
        kw = dict(vocab_size=64, emb_dim=8, deep_layers=(16, 1),
                  mesh=mesh_dp, dtype=jnp.float32)
        m_sh = WideDeep(**kw, replicate_wide=False)
        m_rep = WideDeep(**kw, replicate_wide=True)
        params = jax.jit(m_sh.init)(jax.random.key(0), batch)["params"]

        def loss(module, p):
            return _loss_fn(module, p, batch, None)[0]

        l_sh, g_sh = jax.jit(
            jax.value_and_grad(lambda p: loss(m_sh, p)))(params)
        l_rep, g_rep = jax.jit(
            jax.value_and_grad(lambda p: loss(m_rep, p)))(params)
        np.testing.assert_allclose(np.asarray(l_sh), np.asarray(l_rep),
                                   rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            g_sh, g_rep,
        )

    def test_workload_trains_with_replicated_wide(self, mesh_dp):
        from tests.test_models import run_steps

        wl = get_workload(
            "wide_deep", arch="wide_deep", batch_size=32, vocab_size=64,
            emb_dim=8, mesh=mesh_dp, replicate_wide_table=True,
        )
        state, hist = run_steps(wl, mesh_dp, 4)
        assert np.isfinite([m["loss"] for m in hist]).all()
        # the wide table must be REPLICATED under this mode
        emb = state.params["wide_embed"]["embedding"]
        assert emb.sharding.is_fully_replicated


class TestRecsysWorkloads:
    def _run(self, mesh, arch, n_steps=6):
        from tests.test_models import run_steps

        wl = get_workload(
            "wide_deep", arch=arch, batch_size=32, vocab_size=64,
            emb_dim=8, mesh=mesh,
        )
        return run_steps(wl, mesh, n_steps)

    def test_wide_deep_trains_with_sharded_tables(self, mesh_dp):
        state, hist = self._run(mesh_dp, "wide_deep")
        losses = [m["loss"] for m in hist]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]
        # embedding (and its optimizer state) must be sharded over 'data'
        emb = state.params["deep_embed"]["embedding"]
        assert "data" in tuple(x for x in emb.sharding.spec if x)

    def test_dlrm_trains(self, mesh_dp):
        state, hist = self._run(mesh_dp, "dlrm")
        losses = [m["loss"] for m in hist]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]


class TestMultiTableEmbedding:
    """TPUEmbedding TableConfig/FeatureConfig surface (VERDICT missing #5,
    $TF/python/tpu/tpu_embedding_v2_utils.py:1319,:1538)."""

    def _small_config(self, emb_dim=8, num_sparse=6):
        from distributed_tensorflow_tpu.models.wide_deep import criteo_tables

        return criteo_tables(
            num_sparse, emb_dim, vocab_sizes=(64, 32, 16), embedding_lr=1e-2
        )

    @pytest.fixture
    def mesh_expert(self, devices8):
        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh

        return build_mesh(MeshConfig(data=2, expert=4), devices8)

    def test_lookup_matches_dense_per_table(self, mesh_expert):
        from distributed_tensorflow_tpu.parallel.embedding_config import (
            MultiTableEmbedding,
        )

        fcs = self._small_config()
        mod = MultiTableEmbedding(fcs, mesh=mesh_expert, axis="expert")
        rng = np.random.RandomState(3)
        feats = {
            fc.name: jnp.asarray(
                rng.randint(0, 1 << 20, size=(8,)).astype(np.int32)
            )
            for fc in fcs
        }
        vars_ = jax.jit(mod.init)(jax.random.key(0), feats)
        out = jax.jit(mod.apply)(vars_, feats)
        for fc in fcs:
            table = vars_["params"][fc.table.name]["embedding"]
            ids = feats[fc.name] % fc.table.vocabulary_size
            want = jnp.take(table, ids, axis=0)
            np.testing.assert_allclose(
                np.asarray(out[fc.name]), np.asarray(want), rtol=1e-6
            )

    def test_features_share_tables(self, mesh_expert):
        from distributed_tensorflow_tpu.parallel.embedding_config import (
            MultiTableEmbedding,
        )

        fcs = self._small_config(num_sparse=6)  # 6 features over 3 tables
        mod = MultiTableEmbedding(fcs, mesh=mesh_expert, axis="expert")
        feats = {fc.name: jnp.zeros((4,), jnp.int32) for fc in fcs}
        vars_ = jax.eval_shape(mod.init, jax.random.key(0), feats)
        # exactly 3 parameter tables despite 6 features
        assert sorted(vars_["params"]) == [
            "table_large", "table_medium", "table_small",
        ]

    def test_multivalent_combiner(self, mesh_expert):
        from distributed_tensorflow_tpu.parallel.embedding_config import (
            FeatureConfig,
            MultiTableEmbedding,
            TableConfig,
        )

        t = TableConfig(16, 4, name="t", combiner="mean")
        fcs = (FeatureConfig(table=t, name="f"),)
        mod = MultiTableEmbedding(fcs, mesh=None)
        ids = jnp.asarray([[0, 1, 2], [3, 3, 3]], jnp.int32)  # (B=2, K=3)
        vars_ = mod.init(jax.random.key(0), {"f": ids})
        out = mod.apply(vars_, {"f": ids})
        table = vars_["params"]["t"]["embedding"]
        want = jnp.take(table, ids, axis=0).mean(axis=1)
        assert out["f"].shape == (2, 4)
        np.testing.assert_allclose(np.asarray(out["f"]), np.asarray(want),
                                   rtol=1e-6)

    def test_dlrm_from_config_trains_expert_sharded(self, mesh_expert):
        from tests.test_models import run_steps
        from distributed_tensorflow_tpu.parallel.embedding_config import (
            assert_table_residency,
        )

        fcs = self._small_config()
        wl = get_workload(
            "wide_deep", arch="dlrm", batch_size=32, emb_dim=8,
            num_sparse=len(fcs), feature_configs=fcs, mesh=mesh_expert,
        )
        state, hist = run_steps(wl, mesh_expert, 6)
        losses = [m["loss"] for m in hist]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]
        # every table (not just one) really lives row-sharded on 'expert'
        assert_table_residency(state.params, fcs, axis="expert")

    def test_expert_axis_triggers_multi_table(self, mesh_expert):
        """--expert>1 without explicit configs builds the multi-table DLRM
        on the expert axis (the axis finally earns its place)."""
        wl = get_workload(
            "wide_deep", arch="dlrm", batch_size=32, emb_dim=8,
            num_sparse=6, mesh=mesh_expert,
        )
        assert wl.module.feature_configs is not None  # multi-table DLRM
        assert wl.module.shard_axis == "expert"
        assert wl.make_optimizer is not None  # per-table optimizer wired

    def test_per_table_optimizer_branches(self):
        from distributed_tensorflow_tpu.parallel.embedding_config import (
            multi_table_optimizer,
        )
        import optax

        fcs = self._small_config()
        tx = multi_table_optimizer(fcs, default_tx=optax.sgd(1.0))
        params = {
            "embed": {
                "table_large": {"embedding": jnp.ones((4, 2))},
                "table_medium": {"embedding": jnp.ones((4, 2))},
            },
            "dense": {"kernel": jnp.ones((2, 2))},
        }
        st = tx.init(params)
        grads = jax.tree.map(jnp.ones_like, params)
        updates, _ = tx.update(grads, st, params)
        # sgd(1.0) branch: update == -grad; adagrad branch differs
        np.testing.assert_allclose(
            np.asarray(updates["dense"]["kernel"]), -1.0, rtol=1e-6
        )
        large = np.asarray(updates["embed"]["table_large"]["embedding"])
        assert not np.allclose(large, -1.0)  # took the per-table branch


def _find_masters(opt_state):
    """(path, leaf) pairs of f32-master copies in an optimizer state."""
    flat = jax.tree_util.tree_flatten_with_path(opt_state)[0]
    from distributed_tensorflow_tpu.parallel.sharding import _path_str

    out = []
    for path, leaf in flat:
        p = _path_str(path)
        if "master" in p and p.endswith("embedding"):
            out.append((p, leaf))
    return out


def _overfit_fixed_batch(wl, mesh, n_steps):
    """Train on ONE repeated batch (deterministic decrease — the streaming
    synthetic batches are too noisy at test-sized step counts to assert
    loss ordering on)."""
    import jax
    from distributed_tensorflow_tpu.data import per_host_batch_size
    from distributed_tensorflow_tpu.data.pipeline import make_global_batches
    from distributed_tensorflow_tpu.train_lib import build_state_and_step
    from distributed_tensorflow_tpu.training import BF16

    state, _, step, bsh = build_state_and_step(
        wl, mesh, precision=BF16, total_steps=n_steps)
    batch = next(make_global_batches(
        wl.data_fn(per_host_batch_size(wl.batch_size)),
        bsh[wl.example_key]))
    rng = jax.random.key(0)
    losses = []
    for i in range(n_steps):
        state, m = step(state, batch, jax.random.fold_in(rng, i))
        losses.append(float(m["loss"]))
    return state, losses


class TestBf16Tables:
    """Reduced-precision tables (VERDICT r4 missing #4; TPUEmbedding
    tpu_embedding_v2_utils.py reduced-precision role): rows stored bf16
    (halving gather bytes — the gather-bound roofline's named headroom),
    optimizer accumulation in f32 via the master-weight wrapper."""

    def test_single_table_bf16_trains_with_f32_master(self, mesh_dp):
        wl = get_workload(
            "wide_deep", arch="wide_deep", batch_size=32, vocab_size=64,
            emb_dim=8, mesh=mesh_dp, table_dtype="bf16",
        )
        state, losses = _overfit_fixed_batch(wl, mesh_dp, 12)
        assert np.isfinite(losses).all()
        assert losses[-1] < 0.7 * losses[0], losses
        emb = state.params["deep_embed"]["embedding"]
        assert emb.dtype == jnp.bfloat16
        # dense params stay f32 (only tables are low-precision)
        assert state.params["wide_dense"]["kernel"].dtype == jnp.float32
        masters = _find_masters(state.opt_state)
        assert masters, "no f32 master copies in opt_state"
        by_path = dict(masters)
        deep = [v for p, v in by_path.items() if "deep_embed" in p]
        assert deep and all(v.dtype == jnp.float32 for v in deep)
        # the stored bf16 rows track the master to within one rounding
        m = np.asarray(jax.device_get(deep[0]), np.float32)
        p = np.asarray(jax.device_get(emb), np.float32)
        np.testing.assert_allclose(p, m, atol=float(np.abs(m).max()) / 128)

    def test_multi_table_bf16_trains_expert_sharded(self, devices8):
        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh
        from distributed_tensorflow_tpu.models.wide_deep import criteo_tables
        from distributed_tensorflow_tpu.parallel.embedding_config import (
            assert_table_residency,
        )

        mesh = build_mesh(MeshConfig(data=2, expert=4), devices8)
        fcs = criteo_tables(6, 8, vocab_sizes=(64, 32, 16), dtype=jnp.bfloat16)
        wl = get_workload(
            "wide_deep", arch="dlrm", batch_size=32, emb_dim=8,
            num_sparse=6, feature_configs=fcs, mesh=mesh,
        )
        state, losses = _overfit_fixed_batch(wl, mesh, 12)
        assert np.isfinite(losses).all()
        assert losses[-1] < 0.7 * losses[0], losses
        for t in ("table_large", "table_medium", "table_small"):
            assert state.params["embed"][t]["embedding"].dtype == jnp.bfloat16
        # tables (incl. the f32 masters riding opt_state paths that end in
        # .../embedding) stay row-sharded on expert
        assert_table_residency(state.params, fcs, axis="expert")
        masters = _find_masters(state.opt_state)
        assert len(masters) >= 3, [p for p, _ in masters]
        for p, v in masters:
            assert v.dtype == jnp.float32, p
            spec = v.sharding.spec
            dim0 = spec[0] if len(spec) else None
            dim0 = dim0 if isinstance(dim0, tuple) else (dim0,)
            assert "expert" in dim0, (p, spec)
