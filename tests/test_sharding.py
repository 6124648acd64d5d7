"""Tests for sharding rules / partitioners (SURVEY.md §3.1, §3.4 parity)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.parallel import (
    FixedShardsPartitioner,
    MaxSizePartitioner,
    MinSizePartitioner,
    ShardingRules,
    apply_shardings,
    batch_sharding,
    fsdp_sharding,
    transformer_rules,
)


class TestShardingRules:
    def test_first_match_wins_and_default_replicated(self):
        rules = ShardingRules([
            (r"kernel$", P("fsdp", "tensor")),
            (r".*", P("data")),
        ])
        assert rules.spec_for("dense/kernel", (128, 256)) == P("fsdp", "tensor")
        assert rules.spec_for("dense/bias", (256,)) == P("data")
        assert ShardingRules().spec_for("anything", (4,)) == P()

    def test_spec_trimmed_to_rank(self):
        rules = ShardingRules([(r"kernel", P("fsdp", "tensor"))])
        assert rules.spec_for("kernel", (128,)) == P("fsdp")

    def test_shardings_for_tree(self, mesh_2d):
        rules = ShardingRules([(r"kernel", P(None, "tensor"))])
        tree = {"layer": {"kernel": jnp.ones((4, 8)), "bias": jnp.ones((8,))}}
        sh = rules.shardings_for(mesh_2d, tree)
        assert sh["layer"]["kernel"].spec == P(None, "tensor")
        assert sh["layer"]["bias"].spec == P()
        placed = apply_shardings(tree, sh)
        np.testing.assert_allclose(np.asarray(placed["layer"]["kernel"]),
                                   np.ones((4, 8)))

    def test_transformer_rules_cover_canonical_paths(self):
        rules = transformer_rules()
        assert rules.spec_for("transformer/h_0/attn/c_attn/kernel", (768, 2304)) \
            == P("fsdp", "tensor")
        assert rules.spec_for("transformer/h_0/mlp/c_fc/kernel", (768, 3072)) \
            == P("fsdp", "tensor")
        assert rules.spec_for("wte/embedding", (50257, 768)) == P("tensor", "fsdp")
        assert rules.spec_for("h_0/ln_1/scale", (768,)) == P()


    def test_indivisible_dim_stays_whole_on_a_mesh(self, mesh_2d):
        """Given the mesh, a rule does not split a dimension its axes do not
        divide (jit's out_shardings refuses an uneven split); dims that do
        divide, and every mesh whose axes have size 1, are untouched."""
        rules = ShardingRules([(r"wte", P("tensor", "fsdp"))])
        assert rules.spec_for("wte", (50257, 1024), mesh_2d) \
            == P(None, "fsdp")
        assert rules.spec_for("wte", (50258, 1024), mesh_2d) \
            == P("tensor", "fsdp")
        # compound entries divide by the product of their axes
        rules = ShardingRules([(r"pool", P(("data", "tensor"), None))])
        assert rules.spec_for("pool", (12, 4), mesh_2d) == P(None, None)
        assert rules.spec_for("pool", (16, 4), mesh_2d) \
            == P(("data", "tensor"), None)


class TestFullWidthMeshes:
    """The rule tables at the PUBLISHED widths, which the tiny test configs
    (vocab 256) never exercised: GPT-2 medium's 50257-row ``wte`` under
    ``tensor=2`` used to fail in ``build_state_and_step``.  Shapes only —
    the sharded ``init`` is lowered, never run, so no memory is touched."""

    @pytest.mark.parametrize("axes", [
        {"tensor": 2}, {"fsdp": 2}, {"pipe": 2},
        {"tensor": 2, "fsdp": 2, "pipe": 2},
    ], ids=lambda a: "x".join(f"{k}{v}" for k, v in a.items()))
    def test_gpt2_medium_state_builds(self, devices8, axes):
        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh
        from distributed_tensorflow_tpu.models import get_workload
        from distributed_tensorflow_tpu.parallel.sharding import spec_ways
        from distributed_tensorflow_tpu.train_lib import build_step

        mesh = build_mesh(MeshConfig(**axes), devices8)
        workload = get_workload("gpt2", mesh=mesh, use_flash_attention=True)
        assert workload.module.cfg.vocab_size == 50257
        init, abstract, shardings, _, _ = build_step(
            workload, mesh, grad_accum_steps=workload.grad_accum_steps)
        init.lower()  # an indivisible out_sharding is refused here
        wte, c_attn = (shardings.params[k] for k in ("wte", "blocks"))
        c_attn = c_attn["c_attn"]["kernel"]
        assert spec_ways(mesh, wte.spec[0]) == 1  # 50257 rows: kept whole
        # everything that divides is still split as the table says
        assert spec_ways(mesh, *c_attn.spec) \
            == int(np.prod(list(axes.values())))
        assert abstract.params["wte"].shape == (50257, 1024)

    def test_one_device_specs_are_what_they_were(self):
        """On one device every axis has size 1 and divides everything: the
        repair changes no spec, so one-chip checkpoints restore as before."""
        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh
        from distributed_tensorflow_tpu.models.gpt2 import gpt2_rules

        mesh = build_mesh(MeshConfig(), jax.devices()[:1])
        rules = gpt2_rules()
        for path, shape in (("params/wte", (50257, 1024)),
                            ("params/blocks/c_attn/kernel", (24, 1024, 3072)),
                            ("opt_state/0/mu/wte", (50257, 1024))):
            assert rules.spec_for(path, shape, mesh) \
                == rules.spec_for(path, shape)


class TestFsdpSharding:
    def test_large_params_sharded_small_replicated(self, devices8):
        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh

        mesh = build_mesh(MeshConfig(data=1, fsdp=8), devices8)
        tree = {"big": jnp.ones((1024, 64)), "small": jnp.ones((4, 4))}
        sh = fsdp_sharding(mesh, tree)
        assert sh["big"].spec == P("fsdp")
        assert sh["small"].spec == P()

    def test_indivisible_falls_back_to_replicated(self, devices8):
        from distributed_tensorflow_tpu.cluster import MeshConfig, build_mesh

        mesh = build_mesh(MeshConfig(data=1, fsdp=8), devices8)
        tree = {"odd": jnp.ones((999, 77))}
        sh = fsdp_sharding(mesh, tree, min_size=1)
        assert sh["odd"].spec == P()

    def test_batch_sharding_uses_present_axes(self, mesh_2d):
        sh = batch_sharding(mesh_2d)
        assert sh.spec == P(("data", "fsdp"))


class TestPartitioners:
    def test_fixed_shards(self):
        p = FixedShardsPartitioner(4)
        assert p((100, 16)) == [4, 1]
        assert p((2, 16)) == [2, 1]

    def test_min_size(self):
        # 1M rows x 16 cols x 4B = 64MB; min shard 1MB, up to 8 shards.
        p = MinSizePartitioner(min_shard_bytes=1 << 20, max_shards=8)
        assert p((1 << 20, 16), np.float32) == [8, 1]
        # Tiny variable: one shard.
        assert p((16, 16), np.float32) == [1, 1]

    def test_max_size(self):
        # 64MB total, 16MB cap -> 4 shards.
        p = MaxSizePartitioner(max_shard_bytes=16 << 20)
        assert p((1 << 20, 16), np.float32) == [4, 1]
