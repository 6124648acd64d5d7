"""Whole training programs and layers on the described 2x2 (and the remat'd
stack on one chip), compiled for a described TPU v5e: see
``test_chip_compile.py`` for what a described compile is and why there are
three files of them.  The longest compile comes first and the shortest last
(ROADMAP D8: a worker is handed its next file two tests before the end).
"""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from tests.chip_compile_helpers import (  # noqa: F401 — fixtures by name
    FLASH_KERNELS, V5E_HBM_BYTES, compiled_text, described_mesh, kernel_calls,
    steer_to_the_kernel, topo)

# -- the accumulating step reduces its gradients over `data` once -------------

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def hlo_computations(hlo):
    """name -> text of every computation of a compiled module."""
    return {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%([\w.\-]+) \([^\n]*\{\n(.*?)^\}", hlo, re.M | re.S)}


def inside_loops(comps):
    """The computations that run inside some ``while``: the loops' bodies
    and conditions and whatever those call (the layer loop inside the
    microbatch loop, fusions, reducers)."""
    def called(text):
        names = re.findall(r"(?:body|condition|calls|to_apply)=%([\w.\-]+)",
                           text)
        for branches in re.findall(r"branch_computations=\{([^}]*)\}", text):
            names += re.findall(r"%([\w.\-]+)", branches)
        return names

    todo = [name for text in comps.values()
            for name in re.findall(r"(?:body|condition)=%([\w.\-]+)", text)]
    seen = set()
    while todo:
        name = todo.pop()
        if name in comps and name not in seen:
            seen.add(name)
            todo += called(comps[name])
    return seen


def device_groups(line):
    """A collective's groups of devices, from either form of
    ``replica_groups`` (listed, or an iota ``[groups,size]<=[dims]T(perm)``)
    or from a permute's ``source_target_pairs``."""
    m = re.search(r"(?:replica_groups|source_target_pairs)=\{(\{[\d,{}]*\})\}",
                  line)
    if m:
        return [[int(i) for i in group.split(",")]
                for group in re.findall(r"\{([\d,]+)\}", m.group(1))]
    m = re.search(
        r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?",
        line)
    assert m, f"no device groups in: {line[:200]}"
    ids = np.arange(int(m.group(1)) * int(m.group(2))).reshape(
        [int(d) for d in m.group(3).split(",")])
    if m.group(4):
        ids = ids.transpose([int(d) for d in m.group(4).split(",")])
    return ids.reshape(int(m.group(1)), int(m.group(2))).tolist()


def collectives_over(text, coordinate):
    """(instruction, opcode, result bytes) of each collective in ``text``
    with a group whose devices differ in ``coordinate(device)``."""
    sizes = dict(bf16=2, f32=4, s32=4, u32=4, pred=1, s8=1, u8=1)
    found = []
    for line in text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) (" + "|".join(COLLECTIVES)
            + r")(?:-start)?\(", line)
        if m and any(len({coordinate(d) for d in group}) > 1
                     for group in device_groups(line)):
            nbytes = sum(
                sizes[t] * int(np.prod([int(d) for d in dims.split(",") if d]
                                       or [1]))
                for t, dims in re.findall(
                    r"\b(" + "|".join(sizes) + r")\[([\d,]*)\]", m.group(2)))
            found.append((m.group(1), m.group(3), nbytes))
    return found


def test_accumulating_step_reduces_over_data_once(topo):
    """``train.gpt2-large.d2t2``'s own step (``data=2 x tensor=2``, 64 x
    1024 a step, accumulation 8), whole: the scanned stack compiles in the
    time 4 layers take.  Left to GSPMD the accumulator is replicated over
    ``data`` and every layer's gradient crosses it in every microbatch
    (6.7 GB a chip and step where one reduction moves 1.68).  Each replica
    now sums its own microbatches: no collective inside any ``while`` body
    spans ``data``, the reduction stands once after the loop in f32, the
    three kernels are still called, and the program fits the chip."""
    from benchmark.harness import spec, train

    cell = spec.load_cell("train.gpt2-large.d2t2")
    workload, _, abstract, shardings, step, batch_sh = train.build_step(
        cell, list(topo.devices)[:cell.chips])
    assert step.grad_reduce == "after_scan"
    mesh = batch_sh["tokens"].mesh
    assert dict(mesh.shape)["data"] == 2 and dict(mesh.shape)["tensor"] == 2
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        abstract, shardings)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (int(cell.traffic["batch_size"]), int(cell.traffic["seq_len"])),
        jnp.int32, sharding=batch_sh["tokens"])}
    rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=NamedSharding(mesh, P()))
    compiled = step.lower(state, batch, rng).compile()
    hlo = compiled.as_text()

    # A device's number in the compiled program is its place in the mesh,
    # ``tensor`` minor: its ``data`` coordinate is the quotient.
    data_of = lambda device: device // mesh.shape["tensor"]  # noqa: E731
    comps = hlo_computations(hlo)
    loops = inside_loops(comps)
    assert loops, "no while loop: the layer and microbatch scans are gone"
    in_loops = [c for name in loops
                for c in collectives_over(comps[name], data_of)]
    assert in_loops == [], f"collectives over `data` inside a loop: {in_loops}"
    after = [c for name in set(comps) - loops
             for c in collectives_over(comps[name], data_of)]
    assert {op for _, op, _ in after} == {"all-reduce"}
    grads = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(abstract.params))
    # Each chip's part of the f32 accumulator, once (`tensor` halves all but
    # the embeddings and the vectors), and the loss and aux scalars.
    assert grads * 4 / 2 < sum(n for _, _, n in after) <= grads * 4 + 64
    # ... while the `tensor` axis still works inside the loops.
    tensor_of = lambda device: device % mesh.shape["tensor"]  # noqa: E731
    assert any(collectives_over(comps[name], tensor_of) for name in loops)

    assert set(kernel_calls(hlo)) == FLASH_KERNELS
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < V5E_HBM_BYTES


# -- a remat'd stack runs each of the three kernels once ---------------------

def remat_stack_gpt2(mesh, sharded):
    """Two layers of GPT-2 medium's width, scanned and remat'd by the model
    itself (a small vocabulary: the head is not what is looked at)."""
    from distributed_tensorflow_tpu.models import gpt2

    cfg = dataclasses.replace(
        gpt2.GPT2Config.medium(dropout=0.0, use_flash_attention=True),
        n_layer=2, scan_unroll=1, vocab_size=1024)
    model = gpt2.GPT2(cfg, mesh=mesh)
    batch = {"tokens": np.zeros((8, 1024), np.int32)}
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), batch["tokens"]))["params"]
    return (lambda p, b: gpt2._loss_fn(model, True, p, b, None)[0],
            sharded(params, P()), sharded(batch, P("data")))


def remat_stack_bert(mesh, sharded):
    """The same for BERT base's encoder layer, with a key mask."""
    from distributed_tensorflow_tpu.data.pipeline import synthetic_mlm
    from distributed_tensorflow_tpu.models import bert

    cfg = dataclasses.replace(
        bert.BertConfig.base(dropout=0.0, use_flash_attention=True),
        n_layer=2, scan_unroll=1, vocab_size=1024)
    model = bert.BertPretrain(cfg, mesh=mesh)
    batch = next(synthetic_mlm(batch_size=32, seq_len=512, vocab_size=1024))
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), batch))["params"]
    return (lambda p, b: bert._loss_fn(model, True, p, b, None)[0],
            sharded(params, P()), sharded(batch, P("data")))


@pytest.mark.parametrize("axes", [{}, {"data": 2, "tensor": 2}],
                         ids=["one-chip", "data2xtensor2"])
@pytest.mark.parametrize("stack", [remat_stack_gpt2, remat_stack_bert],
                         ids=["gpt2", "bert-mask"])
def test_remat_stack_runs_each_flash_kernel_once(topo, stack, axes):
    """Whole-block remat ran the forward kernel a second time in the
    backward loop, only to rebuild the two arrays ``_flash_bwd`` needs; the
    layers' remat now keeps them (``fa.REMAT_POLICY``).  Forward and backward
    of the stack as the model builds it: one instruction a kernel, in the
    one program and inside the four-chip ``shard_map``."""
    mesh = described_mesh(topo, **axes)

    def sharded(tree, spec):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=NamedSharding(mesh, P(*spec[:len(a.shape)]))),
            tree)

    loss, params, batch = stack(mesh if axes else None, sharded)
    hlo = compiled_text(jax.grad(loss), params, batch)
    assert kernel_calls(hlo) == dict(flash_dkv=1, flash_dq=1, flash_fwd=1)


# -- one layer under a mesh ----------------------------------------------------

@pytest.mark.parametrize("axes", [{"data": 4}, {"data": 2, "tensor": 2}],
                         ids=["data4", "data2xtensor2"])
def test_gpt2_attention_layer_compiles_on_four_chips(topo, axes):
    """A bare pallas_call under a four-device mesh is refused ("Mosaic
    kernels cannot be automatically partitioned"); the call sites hand the
    kernel to a shard_map over the batch axes and ``tensor``.  One block of
    GPT-2 medium, forward and backward, on the described 2x2."""
    from distributed_tensorflow_tpu.models.gpt2 import Block, GPT2Config

    mesh = described_mesh(topo, **axes)
    cfg = GPT2Config.medium(dropout=0.0, use_flash_attention=True)
    block = Block(cfg, mesh=mesh)
    x = jax.ShapeDtypeStruct(
        (8, 1024, cfg.d_model), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data", None, None)))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, P())),
        jax.eval_shape(block.init, jax.random.key(0),
                       jnp.zeros((8, 1024, cfg.d_model), jnp.bfloat16)))

    def loss(p, h):
        return block.apply(p, h)[0].astype(jnp.float32).sum()

    hlo = compiled_text(jax.grad(loss), params, x)
    assert hlo.count("tpu_custom_call") >= 3
    # Inside the shard_map too the instructions carry the kernels' names.
    assert set(kernel_calls(hlo)) == FLASH_KERNELS


def test_bert_attention_layer_with_mask_compiles_on_four_chips(topo):
    from distributed_tensorflow_tpu.models.bert import BertConfig, EncoderLayer

    mesh = described_mesh(topo, data=2, tensor=2)
    cfg = BertConfig.base(dropout=0.0, use_flash_attention=True)
    layer = EncoderLayer(cfg, mesh=mesh)
    x = jax.ShapeDtypeStruct(
        (32, 512, cfg.d_model), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data", None, None)))
    mask = jax.ShapeDtypeStruct(
        (32, 512), jnp.int32, sharding=NamedSharding(mesh, P("data", None)))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, P())),
        jax.eval_shape(layer.init, jax.random.key(0),
                       jnp.zeros((32, 512, cfg.d_model), jnp.bfloat16)))

    def loss(p, h, m):
        return layer.apply(p, h, m)[0].astype(jnp.float32).sum()

    hlo = compiled_text(jax.grad(loss), params, x, mask)
    assert hlo.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("inner", ["data", "tensor"])
def test_flash_inside_pipeline_stage_compiles_on_four_chips(topo, inner):
    """Inside the pipeline's shard_map (manual over ``pipe`` only) the
    kernel nests a second map over the remaining axes; one GPT-2-medium
    layer per stage, forward and backward through the GPipe schedule."""
    from distributed_tensorflow_tpu.models.gpt2 import (
        Block, GPT2Config, _pipe_stage_fn, _pipe_staging)
    from distributed_tensorflow_tpu.parallel.pipeline import pipeline_apply

    mesh = described_mesh(topo, pipe=2, **{inner: 2})
    cfg = dataclasses.replace(
        GPT2Config.medium(dropout=0.0, use_flash_attention=True), n_layer=2)
    stage_fn = _pipe_stage_fn(cfg, mesh)
    replicated = NamedSharding(mesh, P())
    x = jax.ShapeDtypeStruct((16, 1024, cfg.d_model), jnp.bfloat16,
                             sharding=replicated)

    def one_layer(key):
        return Block(cfg).init(
            key, jnp.zeros((2, 1024, cfg.d_model), jnp.bfloat16))["params"]

    layer = jax.eval_shape(one_layer, jax.random.key(0))
    blocks = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((cfg.n_layer,) + s.shape, s.dtype,
                                       sharding=replicated), layer)

    def loss(p, h):
        staged, xm, _ = _pipe_staging(cfg, mesh, p, h)
        y = pipeline_apply(stage_fn, staged, xm, mesh=mesh, axis="pipe")
        return y.astype(jnp.float32).sum()

    hlo = compiled_text(jax.grad(loss), blocks, x)
    assert kernel_calls(hlo) == dict(flash_dkv=1, flash_dq=1, flash_fwd=1)
