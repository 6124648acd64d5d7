"""Each plain reference against the system at the tiny presets, on the CPU
and in float32 on both sides, so that a wrong reference is found before
chip time is spent: loss, gradients, and logits."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import program, traffic, weights
from benchmark.reference import precision
from benchmark.tests.conftest import FIXTURES


def _load(kind, name):
    with open(os.path.join(FIXTURES, kind, f"{name}.json")) as f:
        return json.load(f)


def _system(config_name, traffic_name, seed=5):
    """The program's module with float32 compute, seeded weights, a batch."""
    import dataclasses

    from distributed_tensorflow_tpu.models import get_workload

    config = _load("configs", config_name)
    mix = _load("traffic", traffic_name)
    cfg = dataclasses.replace(program.program_config(config),
                              dtype=jnp.float32)
    workload = get_workload(
        config["program"]["model"], config=cfg,
        batch_size=mix["batch_size"], seq_len=mix["seq_len"],
        use_flash_attention=False)
    init_input = (workload.init_batch if workload.init_key is None
                  else workload.init_batch[workload.init_key])
    abstract = jax.eval_shape(
        lambda: workload.module.init(jax.random.key(0), init_input))["params"]
    params = weights.make_params(seed, abstract)
    batch = {k: jnp.asarray(v) for k, v in
             next(traffic.batches(mix, seed)).items()}
    return config, workload, params, batch


@pytest.mark.parametrize("config_name,traffic_name", [
    ("gpt2-tiny", "lm-tiny")])
def test_loss_and_gradients_match_the_system(config_name, traffic_name):
    config, workload, params, batch = _system(config_name, traffic_name)
    ref = program.reference_module(config)
    dot = precision.Exact()
    sys_loss, sys_grads = jax.value_and_grad(
        lambda p: workload.eval_loss_fn(p, batch, jax.random.key(0))[0])(params)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: ref.loss(dot, config, p, batch))(params)
    assert float(sys_loss) == pytest.approx(float(ref_loss), abs=2e-5)
    flat_sys = jax.tree_util.tree_leaves_with_path(sys_grads)
    flat_ref = dict(jax.tree_util.tree_leaves_with_path(ref_grads))
    assert len(flat_sys) == len(flat_ref) > 10
    for path, g in flat_sys:
        r = flat_ref[path]
        scale = float(jnp.max(jnp.abs(r))) + 1e-8
        assert float(jnp.max(jnp.abs(g - r))) <= 2e-4 * scale + 1e-7, (
            jax.tree_util.keystr(path))


def test_gpt2_logits_match_the_system():
    config, workload, params, batch = _system("gpt2-tiny", "lm-tiny")
    ref = program.reference_module(config)
    sys_logits = workload.module.apply({"params": params}, batch["tokens"])
    ref_logits = ref.logits(precision.Exact(), config, params, batch["tokens"])
    np.testing.assert_allclose(np.asarray(sys_logits), np.asarray(ref_logits),
                               atol=2e-5, rtol=1e-5)


def test_the_fp8_control_is_a_different_forward():
    config, workload, params, batch = _system("gpt2-tiny", "lm-tiny")
    ref = program.reference_module(config)
    exact = ref.logits(precision.Exact(), config, params, batch["tokens"])
    low = ref.logits(precision.Fp8(), config, params, batch["tokens"])
    gap = float(jnp.max(jnp.abs(exact - low)))
    assert 1e-3 < gap < 1.0
