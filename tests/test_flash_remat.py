"""What a remat'd layer keeps of the flash kernel.

A layer under ``remat`` recomputes itself in the backward pass.  The flash
kernel's forward rule names its two results (``flash_out``, ``flash_lse``)
and the layers' remat saves those names and nothing else
(``ops.flash_attention.REMAT_POLICY``), so the backward pass has the two
arrays ``_flash_bwd`` needs without running the forward kernel again.  The
kept arrays are the ones the recomputation would have made, so loss and
gradients are the same to the bit as under whole-block remat, which the
tests get by putting ``None`` where the models read the policy (the program
has no such option).
"""

import dataclasses
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models import bert, gpt2

fa = importlib.import_module("distributed_tensorflow_tpu.ops.flash_attention")

SEQ = 64


def pallas_calls(jaxpr):
    """Names of the Pallas calls in ``jaxpr``, sub-jaxprs included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names += pallas_calls(sub)
    return names


def tiny_gpt2(dropout):
    cfg = gpt2.GPT2Config.tiny(use_flash_attention=True)
    cfg = dataclasses.replace(cfg, dropout=dropout)
    model = gpt2.GPT2(cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, SEQ), 0, cfg.vocab_size)
    params = model.init(jax.random.key(0), tokens)["params"]
    batch = {"tokens": tokens}
    return gpt2, model, params, batch


def tiny_bert(dropout):
    from distributed_tensorflow_tpu.data.pipeline import synthetic_mlm

    cfg = bert.BertConfig.tiny(use_flash_attention=True)
    cfg = dataclasses.replace(cfg, dropout=dropout)
    model = bert.BertPretrain(cfg)
    batch = {k: jnp.asarray(v) for k, v in next(synthetic_mlm(
        batch_size=2, seq_len=SEQ, vocab_size=cfg.vocab_size)).items()}
    params = model.init(jax.random.key(0), batch)["params"]
    return bert, model, params, batch


MODELS = {"gpt2": tiny_gpt2, "bert": tiny_bert}


def loss_of(module, model, batch, dropout):
    rng = jax.random.key(7)
    return lambda p: module._loss_fn(model, dropout == 0.0, p, batch, rng)[0]


@pytest.mark.parametrize("dropout", [0.0, 0.1], ids=["nodrop", "dropout"])
@pytest.mark.parametrize("path", ["kernel", "dense"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_loss_and_gradients_equal_whole_block_remat_to_the_bit(
        monkeypatch, name, path, dropout):
    """``kernel`` is the Pallas interpreter; with dropout on, its attention
    takes the dense path (the TPU PRNG has no interpreter lowering) while
    the layer's other dropouts stay, so that case holds the policy against
    a layer that draws random numbers and has nothing under the names."""
    if path == "kernel":
        monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
    module, model, params, batch = MODELS[name](dropout)
    # A new function each time: the policy is read while tracing, and a
    # function traced once is not traced again.
    loss, grads = jax.jit(jax.value_and_grad(
        loss_of(module, model, batch, dropout)))(params)
    monkeypatch.setattr(module, "REMAT_POLICY", None)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        loss_of(module, model, batch, dropout)))(params)
    assert np.isfinite(float(loss)) and float(loss) == float(want_loss)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    assert any(np.asarray(g).any() for _, g in flat)
    for (where, got), want in zip(flat, jax.tree.leaves(want_grads)):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want),
            err_msg=jax.tree_util.keystr(where))


@pytest.mark.parametrize("name", sorted(MODELS))
def test_backward_of_the_stack_runs_the_forward_kernel_once(
        monkeypatch, name):
    """The whole model's gradient, as the model builds its scanned and
    remat'd stack: one forward kernel (in the forward scan) where
    whole-block remat has two (the second in the backward scan)."""
    monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
    module, model, params, batch = MODELS[name](0.0)

    def kernels():
        grad = jax.grad(loss_of(module, model, batch, 0.0))
        return sorted(pallas_calls(jax.make_jaxpr(grad)(params).jaxpr))

    assert kernels() == ["flash_dkv", "flash_dq", "flash_fwd"]
    monkeypatch.setattr(module, "REMAT_POLICY", None)
    assert kernels() == ["flash_dkv", "flash_dq", "flash_fwd", "flash_fwd"]


def layer_residuals(capsys, name):
    """What ``jax.checkpoint`` with the models' policy saves of one layer,
    as ``print_saved_residuals`` lists it: one line a residual."""
    from jax.ad_checkpoint import print_saved_residuals

    if name == "gpt2":
        layer = gpt2.Block(gpt2.GPT2Config.tiny(use_flash_attention=True))
    else:
        layer = bert.EncoderLayer(
            bert.BertConfig.tiny(use_flash_attention=True))
    x = jnp.ones((2, SEQ, layer.cfg.d_model), layer.cfg.dtype)
    params = layer.init(jax.random.key(0), x)

    def apply(p, h):
        return layer.apply(p, h)[0].astype(jnp.float32).sum()

    print_saved_residuals(
        jax.checkpoint(apply, prevent_cse=False, policy=fa.REMAT_POLICY),
        params, x)
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines
    return lines


@pytest.mark.parametrize("name", sorted(MODELS))
def test_layer_saves_the_two_names_and_nothing_else_of_its_inside(
        monkeypatch, capsys, name):
    """Beside the layer's own inputs, which any remat saves, two arrays: the
    log-sum-exp under its name and the kernel's output, which the forward
    pass also uses, so remat saves it through a ``reduce_precision`` to its
    own type (an identity that keeps both passes on the same bits)."""
    monkeypatch.setenv("DTT_PALLAS_INTERPRET", "1")
    inside = [line for line in layer_residuals(capsys, name)
              if "from the argument" not in line]
    heads, head_dim = 4, 16
    assert sorted(line.split(" ")[0] for line in inside) == [
        f"bf16[2,{SEQ},{heads},{head_dim}]", f"f32[2,{heads},{SEQ}]"]
    for line in inside:
        assert "ops/flash_attention.py" in line, line
        assert (f"named '{fa.FLASH_LSE}'" in line
                or f"named '{fa.FLASH_OUT}'" in line
                or "output of reduce_precision" in line), line


@pytest.mark.parametrize("name", sorted(MODELS))
def test_layer_on_the_dense_path_has_nothing_under_the_names(capsys, name):
    """A caller whose attention is not the kernel keeps whole-block remat."""
    for line in layer_residuals(capsys, name):
        assert "from the argument" in line, line
