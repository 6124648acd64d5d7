"""The main path's kernels, compiled for a TPU v5e that is described, not
attached (on-chip-measurement guide, section 2).

Every other kernel test runs in the Pallas interpreter, which cannot see what
the chip's compiler refuses: a block that does not tile, too much VMEM, a
Mosaic kernel handed to the automatic partitioner.  These cases ask the real
compiler, at real widths, for about two seconds each and no chip time.
Nothing runs, so nothing here says anything about results or speed.

All cases live in this one file: the compile-only libtpu is loaded once per
process, and ``ALLOW_MULTIPLE_LIBTPU_LOAD`` lets parallel test workers load it
side by side (no chip is held, so the multi-process lock protects nothing).
"""

import dataclasses
import importlib
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.cluster.topology import MESH_AXES

fa = importlib.import_module("distributed_tensorflow_tpu.ops.flash_attention")
pa = importlib.import_module("distributed_tensorflow_tpu.ops.paged_attention")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it cannot describe
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    # A described-device executable is written to the persistent cache but
    # cannot be read back without a chip; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(autouse=True)
def steer_to_the_kernel(monkeypatch):
    """``jax.devices()`` is the CPU here, so the kernel's own platform check
    would pick the dense path; the test steers it, the program has no such
    option."""
    monkeypatch.delenv("DTT_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_platform", lambda: "tpu")


def described_mesh(topo, **axes):
    shape = tuple(axes.get(a, 1) for a in MESH_AXES)
    devices = np.array(topo.devices[:int(np.prod(shape))])
    return Mesh(devices.reshape(shape), MESH_AXES,
                axis_types=(AxisType.Auto,) * len(MESH_AXES))


def compiled_text(fn, *structs):
    return jax.jit(fn).lower(*structs).compile().as_text()


def kernel_names(hlo):
    """The names the compiled program gives its Pallas calls: the profiler
    shows an executed instruction by this text, so a kernel's ``name=`` is
    how a device trace tells the three apart.  The compiler names the
    instruction after the innermost scope of its ``op_name``:
    ``%flash_fwd.3`` under a module's scope, ``%jvp_flash_fwd_.1`` bare."""
    calls = kernel_calls(hlo)     # below, with the counts
    return set(calls)


def one_chip(topo, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(
        shape, dtype,
        sharding=jax.sharding.SingleDeviceSharding(topo.devices[0]))


GPT2_MEDIUM = (8, 1024, 16, 64)   # one grad-accum microbatch of 32/4
GPT2_LARGE_D2T2 = (4, 1024, 10, 64)  # train.gpt2-large.d2t2, one chip's part
GPT2_LONG = (1, 8192, 16, 64)     # past the resident-VMEM schedule
BERT_BASE = (32, 512, 12, 64)
BERT_SEQ128 = (64, 128, 12, 64)
BERT_SEQ768 = (8, 768, 12, 64)    # one whole 768 x 768 tile a head
RING_BLOCK = (4, 512, 16, 64)     # one context=2 shard of seq 1024

# (id, shape, causal, kv_mask, dropout, backward, kernel calls expected)
KERNEL_CASES = [
    ("gpt2-fwd", GPT2_MEDIUM, True, False, 0.0, False, 1),
    ("gpt2-bwd", GPT2_MEDIUM, True, False, 0.0, True, 3),
    ("gpt2-bwd-dropout", GPT2_MEDIUM, True, False, 0.1, True, 3),
    ("gpt2-large-d2t2-bwd", GPT2_LARGE_D2T2, True, False, 0.0, True, 3),
    ("gpt2-long-bwd", GPT2_LONG, True, False, 0.0, True, 3),
    ("bert-fwd-mask", BERT_BASE, False, True, 0.0, False, 1),
    ("bert-bwd-mask", BERT_BASE, False, True, 0.0, True, 3),
    ("bert-bwd-mask-dropout", BERT_BASE, False, True, 0.1, True, 3),
    ("bert128-bwd-mask", BERT_SEQ128, False, True, 0.0, True, 3),
    ("bert768-bwd", BERT_SEQ768, False, False, 0.0, True, 3),
]


@pytest.mark.parametrize(
    "shape,causal,masked,dropout,backward,calls",
    [c[1:] for c in KERNEL_CASES], ids=[c[0] for c in KERNEL_CASES])
def test_flash_kernel_compiles_for_v5e(topo, shape, causal, masked, dropout,
                                       backward, calls):
    B, T, _, _ = shape
    qkv = one_chip(topo, shape)
    mask = one_chip(topo, (B, T), jnp.int32) if masked else None
    rng = jax.random.key(0) if dropout else None

    def attend(q, k, v, m):
        return fa.flash_attention(
            q, k, v, causal=causal, kv_mask=m, dropout_rate=dropout,
            dropout_rng=rng).astype(jnp.float32).sum()

    fn = jax.grad(attend, argnums=(0, 1, 2)) if backward else attend
    hlo = compiled_text(fn, qkv, qkv, qkv, mask)
    assert hlo.count("tpu_custom_call") >= calls, (
        "the Pallas kernel is not in the compiled program")
    assert kernel_names(hlo) == (
        {"flash_fwd", "flash_dq", "flash_dkv"} if backward else {"flash_fwd"})


def test_ring_block_kernel_with_lse_cotangent_compiles_for_v5e(topo):
    """What ring attention consumes per kv block: (out, lse), both with a
    cotangent, so the backward kernels take the g_lse operand."""
    qkv = one_chip(topo, RING_BLOCK)

    def block(q, k, v):
        out, lse = fa.flash_attention_with_lse(q, k, v, causal=False)
        return out.astype(jnp.float32).sum() + lse.sum()

    hlo = compiled_text(jax.grad(block, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert hlo.count("tpu_custom_call") >= 3


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
    assert kernel_names(hlo) == {"flash_fwd", "flash_dq", "flash_dkv"}


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


# -- the server's programs: the paged KV pools are updated in place ----------

# serve.gpt2-medium.chat-saturated: slots x 1024 positions in blocks of 16,
# a full pool plus the trash block, 4 fused decode steps, one-slot prefills.
SERVE_TOTAL_LEN, SERVE_BLOCK, SERVE_MEGASTEP, SERVE_PROMPT = 1024, 16, 4, 128
V5E_HBM_BYTES = 15.75e9


def lower_serve_program(topo, program, slots, module=None,
                        prompt=SERVE_PROMPT, served=True):
    """The engine's own ``decode_megastep`` or ``prefill_slots`` program for
    GPT-2 medium (or ``module``), lowered from shapes alone.
    ``ServeEngine()`` places real weights, which a described device cannot
    hold, so the two ``_apply`` methods run on a bare instance that has
    only the module they read.  The parameters are given in the types the
    engine holds them in (GPT-2's by the family's ``served_dtypes``; the
    other families' as declared); ``served=False`` gives the checkpoint's
    float32, what every program took up to PR 38."""
    from distributed_tensorflow_tpu.models import get_workload
    from distributed_tensorflow_tpu.models.gpt2 import (
        GPT2, GPT2Config, PagedKVConfig)
    from distributed_tensorflow_tpu.serve import sampling as sampling_lib
    from distributed_tensorflow_tpu.serve import engine as engine_lib
    from distributed_tensorflow_tpu.serve.engine import ServeEngine

    typed = None
    if module is None:
        module = GPT2(GPT2Config.medium(dropout=0.0))
        if served:
            typed = get_workload("gpt2", config=module.cfg).served_dtypes
    engine = object.__new__(ServeEngine)
    engine.module = module
    max_blocks = SERVE_TOTAL_LEN // SERVE_BLOCK
    paged = PagedKVConfig(block_size=SERVE_BLOCK,
                          num_blocks=slots * max_blocks + 1)

    def arg(shape, dtype=jnp.int32):
        return one_chip(topo, shape, dtype)

    variables = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((slots, SERVE_TOTAL_LEN), jnp.int32),
        decode=True, slot_ids=jnp.arange(slots, dtype=jnp.int32),
        paged=paged, block_tables=jnp.zeros((slots, max_blocks), jnp.int32)))
    declared = jax.tree.map(lambda s: s.dtype, variables["params"])
    params, cache = jax.tree.map(
        lambda s, dtype: arg(s.shape, dtype),
        (variables["params"], variables["cache"]),
        (typed(variables["params"]) if typed else declared,
         jax.tree.map(lambda s: s.dtype, variables["cache"])))
    counts = arg((slots, module.cfg.vocab_size))
    tables = arg((slots, max_blocks))
    rng = arg((), jax.random.key(0).dtype)

    def sampling(rows):
        return jax.tree.map(
            lambda a: arg(np.shape(a), np.asarray(a).dtype),
            sampling_lib.uniform(rows, 0.0, 0))

    if program == "decode_megastep":
        # As ``decode_megastep`` picks it: a cache that counts the router's
        # choices gets the program that returns what a launch added.
        apply = (engine._megastep_apply
                 if engine_lib.moe_counts_of(cache) is None
                 else engine._megastep_counting_apply)
        fn = jax.jit(
            lambda *a: apply(SERVE_MEGASTEP, paged, *a),
            donate_argnums=(1, 2))
        lowered = fn.lower(
            params, cache, counts, arg((slots,)), arg((slots,), jnp.bool_),
            arg((slots,)), arg((slots,)), tables, rng, arg(()),
            sampling(slots), arg((slots,)), arg((slots,), jnp.bool_),
            arg(()))
    else:
        fn = jax.jit(
            lambda *a: engine._prefill_slots_apply(paged, *a),
            donate_argnums=(1, 2))
        lowered = fn.lower(
            params, cache, counts, arg((1, prompt)), arg((1,)), tables,
            rng, arg(()), arg((1,)), sampling(1), arg((1,), jnp.bool_))
    pool = (cache["latent_pool"] if "latent_pool" in cache
            else cache["blocks"]["cached_key_pool"]).shape
    return lowered, pool


def pool_sized_results(hlo, pool):
    """(instruction, opcode, line) of every instruction whose result is a
    whole pool, a slab of some of its layers, or one layer of it."""
    tail = ",".join(str(n) for n in pool[1:])
    shaped = re.compile(
        rf"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[(?:\d+,)*{tail}\]\S* ([\w\-]+)\(")
    out = []
    for line in hlo.splitlines():
        m = shaped.match(line)
        if m:
            out.append((m.group(1), m.group(2), line))
    return out


def fused_computation(hlo, line):
    name = re.search(r"calls=%([\w.\-]+)", line).group(1)
    body = hlo.split(f"%{name} (", 1)[1]
    return body.split("\n}\n", 1)[0]


def assert_only_scatters_produce_pools(hlo, pool):
    produced = pool_sized_results(hlo, pool)
    assert any(op == "scatter" for _, op, _ in produced)
    for name, op, line in produced:
        if op == "fusion":
            assert " scatter(" in fused_computation(hlo, line), (
                f"%{name} makes a pool-sized array and is no scatter")
        else:
            assert op in ("parameter", "get-tuple-element", "scatter",
                          "bitcast"), f"%{name} is a pool-sized {op}"


@pytest.mark.parametrize("program", ["decode_megastep", "prefill_slots"])
def test_serve_program_updates_the_kv_pools_in_place(topo, program):
    """With the pools scanned over the layer axis, or stored with the head
    size of 64 in the minor dimension, each token step sliced, re-laid and
    re-stacked both pools: 11.5 GB of scratch for 1.6 GB of cache.  Carried
    through the layer loop and stored lane-dense, the only instructions
    that produce a pool are the scatters, on the program's own argument:
    no copy, no ``AllocateBuffer`` custom-call, no other fusion."""
    lowered, pool = lower_serve_program(topo, program, slots=16)
    compiled = lowered.compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
    hlo = compiled.as_text()
    assert_only_scatters_produce_pools(hlo, pool)


def test_serve_decode_attention_reads_the_pools_where_they_lie(topo):
    """The gather path read every slot's whole table row: a gathered
    ``bf16[32,64,16,1024]`` a layer and pool, re-laid by XLA to
    ``bf16[32,1024,16,64]`` with the head size padded to 128 lanes (10 of a
    step's 29 ms at 32 slots).  The decode program now hands both pools to
    the block-table kernel as they are: the call is there under its name,
    nothing has a head's 64 columns in its minor dimension over a slot's
    1,024 positions, the pools are still only produced by the scatters (a
    layout the kernel did not share would show as a copy of 3.2 GB a call),
    and the scratch is smaller than the gather path's 0.95 GB."""
    slots = 32
    with pa.record_paths() as paths:
        lowered, pool = lower_serve_program(topo, "decode_megastep", slots)
    assert pa.KERNEL in paths     # (the init call that sizes the cache gathers)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    calls = re.findall(
        r"%(paged_decode_attn[\w.]*) = [^\n]*tpu_custom_call", hlo)
    assert calls, "no tpu_custom_call named paged_decode_attn"
    heads, head_dim = 16, 64
    rows = slots * SERVE_TOTAL_LEN * heads
    for dims in re.findall(r"= \w+\[([\d,]+)\]", hlo):
        shape = [int(n) for n in dims.split(",")]
        assert not (shape[-1] == head_dim
                    and int(np.prod(shape[:-1])) == rows), (
            f"an array of shape {shape}: a slot's whole row, head-minor")
    assert_only_scatters_produce_pools(hlo, pool)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


def test_serve_prefill_program_keeps_the_gather_path(topo):
    """Only the decode shape takes the kernel: a prefill's queries are
    many positions a row."""
    with pa.record_paths() as paths:
        lowered, _ = lower_serve_program(topo, "prefill_slots", slots=32)
    assert set(paths) == {pa.GATHER}
    assert "paged_decode_attn" not in lowered.as_text()


def test_serve_decode_program_fits_one_chip_at_64_slots(topo):
    """The compiler refused this size while every step copied the pools
    ("Used 23.19G of 15.75G hbm")."""
    lowered, _ = lower_serve_program(topo, "decode_megastep", slots=64)
    memory = lowered.compile().memory_analysis()
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < V5E_HBM_BYTES


# A GPT-2 medium layer's four kernels, as the HLO's shapes spell them.
LAYER_KERNELS = "1024,3072|1024,1024|1024,4096|4096,1024"


@pytest.mark.parametrize("program", ["decode_megastep", "prefill_slots"])
def test_serve_programs_read_the_weights_once_in_the_compute_type(topo,
                                                                  program):
    """Up to PR 38 every launch converted all 24 layers' float32 kernels
    and the embedding to bfloat16 (1.41 GB read, 0.71 GB written, 0.78 GB
    of the decode program's scratch) and then took four layers' kernels at
    a time out of the converted stack.  With the arguments as the engine
    holds them, at the cell's 64 slots: no float32 value of a weight's
    shape is left in the program and nothing converts to one; **no
    four-layer slice of the kernels exists, in any memory space** (while
    the stack's scan sliced them, the decode program staged them in fast
    memory, ``S(1)``, and the prefill program, whose fast memory the
    prefetched embedding fills, copied them in HBM: 0.6 GB a launch); each
    layer's kernel is sliced by the layer's index inside the fusion of the
    product that reads it; and the scratch is what the activations need."""
    lowered, _ = lower_serve_program(topo, program, slots=64)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert not re.search(
        rf"f32\[(?:\d+,)*(?:{LAYER_KERNELS})\]|f32\[50257,1024\]", hlo)
    assert not re.search(
        rf"= bf16\[(?:\d+,)*(?:{LAYER_KERNELS}|50257,1024)\]\S* convert\(",
        hlo)
    slabs = re.findall(rf"= (bf16\[4,(?:{LAYER_KERNELS})\]\S*) ", hlo)
    assert slabs == [], f"four layers' kernels sliced out together: {slabs}"
    for kernel in LAYER_KERNELS.split("|"):
        assert re.search(
            rf"= bf16\[1,{kernel}\]\S* dynamic-slice\(", hlo), kernel
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes < 7.2e9    # 7.88e9 in float32
    assert memory.temp_size_in_bytes < 0.1e9        # 0.78e9 and 0.61e9


# -- the latent-attention, sparse-expert family at its cell's shapes ----------

# The three cells whose programs run expert layers: (held, tokens of a
# layer's call, 2 x the experts' width) of a decode step and of the longest
# prefill launch; the scratch (bytes) of the decode and the prefill program
# while every held expert ran over every token (my described-v5e compiles
# of the parent's form, PR 41).
GLM_47, MELLUM, GLM_52 = "glm-4.7-flash", "mellum2", "glm-5.2"
EVERY_EXPERT_OVER_EVERY_TOKEN = {
    GLM_47: ((8, 16, 3072), (8, 384, 3072), 431_387_136, 44_919_808),
    MELLUM: ((16, 16, 1792), (16, 512, 1792), 357_766_144, 632_185_856),
    GLM_52: ((8, 16, 4096), (8, 1024, 4096), 538_529_792, 407_471_104),
}


def assert_each_assignment_once(compiled, cell, program):
    """At the cells' shapes both programs of each family take the grouped
    form (``ops/grouped_matmul.py``): the two kernels are in the program,
    once a layer of the loop's body or of the unrolled stack; no float32
    ``(held, tokens, 2f)`` result exists (all the held experts' gate and up
    products over all the tokens: 134 MB a layer of the sixth cell's
    chunk), in any layout; and the program's scratch is what it was with
    that form or less (a prefill launch 0.3-42 MB less; a decode program
    the same to a thousandth, the 0.1-0.4 MB of a step's rounded ``silu(g)
    * u`` rows over, which the dense form kept inside one fusion).  Bytes
    and names, never a rate.  Called where each cell's test has its
    program compiled: a second compile is half a minute, and a compiled
    program kept for a later test would outlive its own."""
    decode, prefill, decode_scratch, prefill_scratch = (
        EVERY_EXPERT_OVER_EVERY_TOKEN[cell])
    hlo = compiled.as_text()
    layers = 4 if cell != GLM_47 else 1     # a period, the stack, the body
    for kernel in ("expert_gate_up", "expert_down"):
        calls = re.findall(rf"%({kernel}[\w.]*) = [^\n]*tpu_custom_call", hlo)
        assert len(calls) == layers, (kernel, calls)
    for shape in (decode, prefill):
        dims = ",".join(str(n) for n in shape)
        assert f"f32[{dims}]" not in hlo
    scratch = compiled.memory_analysis().temp_size_in_bytes
    if program == "decode_megastep":
        assert scratch <= 1.002 * decode_scratch
    else:
        assert scratch < prefill_scratch


def glm_cell():
    """``serve.glm-4.7-flash.reason-saturated``: its module and scheduler."""
    from benchmark.harness import program, spec
    from distributed_tensorflow_tpu.models.glm4_moe_lite import Glm4MoeLite

    cell = spec.load_cell("serve.glm-4.7-flash.reason-saturated")
    sched = cell.cell["scheduler"]
    assert (sched["max_total_len"], sched["block_size"], sched["megastep"]) \
        == (SERVE_TOTAL_LEN, SERVE_BLOCK, SERVE_MEGASTEP)
    return Glm4MoeLite(program.program_config(cell.config)), cell


@pytest.mark.parametrize("program", ["decode_megastep", "prefill_slots"])
def test_latent_serve_programs_fit_one_chip_at_the_cells_shapes(topo, program):
    """One chip's share of the expert-parallel deployment, 1 dense + 20
    expert layers at the published widths: 4.60 GB of bfloat16 weights and
    a latent pool of 16 slots x 1,024 positions x 21 layers x 640 values
    (0.44 GB), updated in place; the decode program (4 fused steps, with
    the router's counts as one more output) adds 0.43 GB of scratch, the
    longest prefill (384 positions) 0.04 GB.  PERF.md section 4 quotes
    these figures."""
    module, cell = glm_cell()
    slots = int(cell.cell["scheduler"]["num_slots"])
    longest = max(cell.traffic["prompt_tokens"]["round_up_to"])
    lowered, pool = lower_serve_program(topo, program, slots, module=module,
                                        prompt=longest)
    assert pool == (21, slots * 64 + 1, 16, 640)
    compiled = lowered.compile()
    assert_each_assignment_once(compiled, GLM_47, program)
    memory = compiled.memory_analysis()
    assert 5.0e9 < memory.argument_size_in_bytes < 5.1e9
    assert memory.temp_size_in_bytes < (0.5e9 if program == "decode_megastep"
                                        else 0.06e9)
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < V5E_HBM_BYTES
    assert_only_scatters_produce_pools(compiled.as_text(), pool)


# -- a remat'd stack runs each of the three kernels once ---------------------

def kernel_calls(hlo):
    """How many instructions carry each kernel's name."""
    calls = re.findall(
        r"%\w*?(flash_(?:fwd|dq|dkv))[\w.]* = [^\n]*tpu_custom_call", hlo)
    return {name: calls.count(name) for name in sorted(set(calls))}


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

    assert kernel_names(hlo) == {"flash_fwd", "flash_dq", "flash_dkv"}
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < V5E_HBM_BYTES


# -- the grouped-query, window-and-full family at its cell's shapes -----------

def lower_two_pool_program(topo, program):
    """``serve.mellum2-12b-a2.5b.code-mixed-saturated``'s decode or prefill
    program, lowered from shapes as ``lower_serve_program`` does, with the
    scheduler's two tables a slot (the full layers' entries, then the
    window ring's) and the cell's own lengths."""
    from benchmark.harness import program as program_lib, spec
    from distributed_tensorflow_tpu.models import PagedKVConfig
    from distributed_tensorflow_tpu.models.mellum import Mellum
    from distributed_tensorflow_tpu.serve import sampling as sampling_lib
    from distributed_tensorflow_tpu.serve.engine import ServeEngine

    cell = spec.load_cell("serve.mellum2-12b-a2.5b.code-mixed-saturated")
    sched = cell.cell["scheduler"]
    slots, total, block = (sched["num_slots"], sched["max_total_len"],
                           sched["block_size"])
    chunk, steps = sched["prefill_budget"], sched["megastep"]
    module = Mellum(program_lib.program_config(cell.config))
    engine = object.__new__(ServeEngine)
    engine.module = module
    per_slot = total // block
    ring = -(-(module.cfg.sliding_window + chunk + steps) // block) + 1
    paged = PagedKVConfig(
        block_size=block, num_blocks=slots * per_slot + 1,
        window_blocks=slots * ring + 1, window_ring=ring)
    width = paged.table_width(total)

    def arg(shape, dtype=jnp.int32):
        return one_chip(topo, shape, dtype)

    variables = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((slots, total), jnp.int32),
        decode=True, slot_ids=jnp.arange(slots, dtype=jnp.int32),
        paged=paged, block_tables=jnp.zeros((slots, width), jnp.int32)))
    params, cache = jax.tree.map(
        lambda s: arg(s.shape, s.dtype),
        (variables["params"], variables["cache"]))
    counts = arg((slots, module.cfg.vocab_size))
    tables = arg((slots, width))
    rng = arg((), jax.random.key(0).dtype)
    sampling = lambda rows: jax.tree.map(
        lambda a: arg(np.shape(a), np.asarray(a).dtype),
        sampling_lib.uniform(rows, 0.0, 0))
    if program == "decode_megastep":
        fn = jax.jit(
            lambda *a: engine._megastep_counting_apply(steps, paged, *a),
            donate_argnums=(1, 2))
        lowered = fn.lower(
            params, cache, counts, arg((slots,)), arg((slots,), jnp.bool_),
            arg((slots,)), arg((slots,)), tables, rng, arg(()),
            sampling(slots), arg((slots,)), arg((slots,), jnp.bool_),
            arg(()))
    else:
        fn = jax.jit(
            lambda *a: engine._prefill_slots_apply(paged, *a),
            donate_argnums=(1, 2))
        lowered = fn.lower(
            params, cache, counts, arg((1, chunk)), arg((1,)), tables,
            rng, arg(()), arg((1,)), sampling(1), arg((1,), jnp.bool_))
    return lowered, cache, slots


@pytest.mark.parametrize("program", ["decode_megastep", "prefill_slots"])
def test_two_pool_serve_programs_fit_one_chip_at_the_cells_shapes(topo,
                                                                  program):
    """One chip's share of the 4-chip deployment, 16 layers at the
    published widths: 4.08 GB of bfloat16 weights, the 4 full layers' pool
    of ``slots x 256 + 1`` blocks and the 12 window layers' of ``slots x
    98 + 1`` (a ring a slot, whatever the row's length), both updated in
    place; at 16 slots 5.24 GB of arguments, and 0.36 GB of scratch for the
    decode program (4 fused steps), 0.63 GB for a prefill chunk of 512 (one
    slot's, whatever the slots).  A scanned body that slices a whole
    period's leaves out of the layer stack copies them (0.8 GB of expert
    stacks: 1.28 and 1.51 GB of scratch at 8 slots, and half of a decode
    step's time on the chip; PERF.md Findings, PR 35): the scratch bound
    below is what catches it.  The cell's ``num_slots_arithmetic`` and
    PERF.md section 4 quote these figures.

    The decode program reads both pools where they lie: one call of the
    grouped block-table kernel a layer of the scanned period's body (the
    fused steps are a loop round it), and none of what the gather path made
    of a full layer's table rows (0.58 GB of scratch; PERF.md Findings,
    PR 36): the gathered ``bf16[4096,16,1024]`` (16 slots x 256 blocks), K
    and V split out of it and re-laid as ``bf16[16,4096,512]``, nor the
    window layers' rings (16 x 98 blocks).  A prefill chunk is many
    positions a row and keeps the gather path."""
    lowered, cache, slots = lower_two_pool_program(topo, program)
    full, window = cache["full_pool"].shape, cache["window_pool"].shape
    assert full == (4, slots * 256 + 1, 16, 1024)
    assert window == (12, slots * 98 + 1, 16, 1024)
    compiled = lowered.compile()
    assert_each_assignment_once(compiled, MELLUM, program)
    memory = compiled.memory_analysis()
    pools = 2 * (np.prod(full) + np.prod(window))
    assert 4.07e9 + pools < memory.argument_size_in_bytes < 4.10e9 + pools
    assert memory.temp_size_in_bytes < 0.8e9     # the slabs alone are 0.8
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < V5E_HBM_BYTES
    hlo = compiled.as_text()
    assert_only_scatters_produce_pools(hlo, full)
    assert_only_scatters_produce_pools(hlo, window)
    assert not kernel_calls(hlo)        # no flash kernel in a serving step
    calls = re.findall(
        r"%(paged_decode_attn[\w.]*) = [^\n]*tpu_custom_call", hlo)
    if program == "prefill_slots":
        assert not calls                # the gather path: no Pallas call
        return
    period = 4                          # three window layers and one full
    assert len(calls) == period
    assert all(name.startswith("paged_decode_attn_gqa") for name in calls)
    assert memory.temp_size_in_bytes < 0.45e9    # the gather path: 0.58
    gathered = {(slots * 256, 16, 1024), (slots, 256 * 16, 512),
                (slots * 98, 16, 1024), (slots, 98 * 16, 512)}
    for dims in re.findall(r"= bf16\[([\d,]+)\]", hlo):
        shape = tuple(int(n) for n in dims.split(","))
        assert shape not in gathered, (
            f"a bf16{list(shape)}: every slot's table rows or rings, "
            f"gathered or split")


# -- the learned-sparse-attention family at its cell's shapes ------------------

def lower_sparse_latent_program(topo, program):
    """``serve.glm-5.2.longdoc-saturated``'s decode or prefill program,
    lowered from shapes as ``lower_serve_program`` does, at the cell's own
    slots, lengths and chunk: one table a slot for both pools."""
    from benchmark.harness import program as program_lib, spec
    from distributed_tensorflow_tpu.models import PagedKVConfig
    from distributed_tensorflow_tpu.models.glm_moe_dsa import GlmMoeDsa
    from distributed_tensorflow_tpu.serve import sampling as sampling_lib
    from distributed_tensorflow_tpu.serve.engine import ServeEngine

    cell = spec.load_cell("serve.glm-5.2.longdoc-saturated")
    sched = cell.cell["scheduler"]
    slots, total, block = (sched["num_slots"], sched["max_total_len"],
                           sched["block_size"])
    chunk, steps = sched["prefill_budget"], sched["megastep"]
    module = GlmMoeDsa(program_lib.program_config(cell.config))
    engine = object.__new__(ServeEngine)
    engine.module = module
    per_slot = total // block
    paged = PagedKVConfig(block_size=block, num_blocks=slots * per_slot + 1)

    def arg(shape, dtype=jnp.int32):
        return one_chip(topo, shape, dtype)

    variables = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((slots, total), jnp.int32),
        decode=True, slot_ids=jnp.arange(slots, dtype=jnp.int32),
        paged=paged, block_tables=jnp.zeros((slots, per_slot), jnp.int32)))
    params, cache = jax.tree.map(
        lambda s: arg(s.shape, s.dtype),
        (variables["params"], variables["cache"]))
    counts = arg((slots, module.cfg.vocab_size))
    tables = arg((slots, per_slot))
    rng = arg((), jax.random.key(0).dtype)
    sampling = lambda rows: jax.tree.map(
        lambda a: arg(np.shape(a), np.asarray(a).dtype),
        sampling_lib.uniform(rows, 0.0, 0))
    if program == "decode_megastep":
        fn = jax.jit(
            lambda *a: engine._megastep_counting_apply(steps, paged, *a),
            donate_argnums=(1, 2))
        lowered = fn.lower(
            params, cache, counts, arg((slots,)), arg((slots,), jnp.bool_),
            arg((slots,)), arg((slots,)), tables, rng, arg(()),
            sampling(slots), arg((slots,)), arg((slots,), jnp.bool_),
            arg(()))
    else:
        fn = jax.jit(
            lambda *a: engine._prefill_slots_apply(paged, *a),
            donate_argnums=(1, 2))
        lowered = fn.lower(
            params, cache, counts, arg((1, chunk)), arg((1,)), tables,
            rng, arg(()), arg((1,)), sampling(1), arg((1,), jnp.bool_))
    return lowered, cache, slots, chunk


@pytest.mark.parametrize("program", ["decode_megastep", "prefill_slots"])
def test_sparse_latent_serve_programs_fit_one_chip_at_the_cells_shapes(
        topo, program):
    """One chip's share of the v5e-256 deployment, published layers 2-6 at
    the published widths: 5.35 GB of bfloat16 weights, the five layers'
    latent pool of ``slots x 512 + 1`` blocks (0.84 GB at 16 slots) and the
    two ``full`` layers' index keys under the same block numbers (0.07
    GB), both updated in place: 6.25 GB of arguments; 0.54 GB of scratch
    for the decode program (4 fused steps, the router's counts as one more
    output) and 0.41 GB for a prefill chunk of 1,024 (one slot's).  Here
    the decode program compiled in 22 s and the chunk in 31 s (PERF.md
    section 4 and the cell's ``num_slots_arithmetic`` quote these).

    A decode step gathers the selected rows and no table row: the only
    ``(slots, ., 640)`` arrays are ``index_topk`` long, and what it reads
    of the index keys is a chunk of the context a turn of the walk, never
    the 512 blocks of a row at once."""
    import time

    lowered, cache, slots, chunk = lower_sparse_latent_program(topo, program)
    latent, index = cache["latent_pool"].shape, cache["index_pool"].shape
    assert latent == (5, slots * 512 + 1, 16, 640)
    assert index == (2, slots * 512 + 1, 16, 128)
    started = time.perf_counter()
    compiled = lowered.compile()
    assert time.perf_counter() - started < 240      # a cold start pays it
    assert_each_assignment_once(compiled, GLM_52, program)
    memory = compiled.memory_analysis()
    pools = 2 * (np.prod(latent) + np.prod(index))
    assert 5.34e9 + pools < memory.argument_size_in_bytes < 5.36e9 + pools
    assert memory.temp_size_in_bytes < (
        0.65e9 if program == "decode_megastep" else 0.5e9)
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < V5E_HBM_BYTES
    hlo = compiled.as_text()
    # In place: nothing but a scatter makes a pool-sized array (a chunk's
    # scatter is written over the pool's rows flattened and shows here as
    # a bitcast of its fusion).
    assert " scatter(" in hlo
    for pool in (latent, index):
        for name, op, line in pool_sized_results(hlo, pool):
            assert op in ("parameter", "get-tuple-element", "bitcast",
                          "scatter") or (
                op == "fusion" and " scatter(" in fused_computation(
                    hlo, line)), f"%{name} is a pool-sized {op}"
    assert not kernel_calls(hlo)        # no flash kernel in a serving step
    shapes = {tuple(int(n) for n in dims.split(","))
              for dims in re.findall(r"= bf16\[([\d,]+)\]", hlo)}
    whole_rows = {(slots * 512, 16, 640), (slots, 8192, 640),
                  (slots * 512, 16, 128), (slots, 8192, 128),
                  (512, 16, 640), (1, 8192, 640), (1, 8192, 128)}
    assert not shapes & whole_rows, shapes & whole_rows
    if program == "decode_megastep":
        assert (slots, 2048, 640) in shapes      # the selected rows
        assert (slots, 1024, 128) in shapes      # a turn's index keys
    else:
        assert (1, 1024, 640) in shapes          # a turn's latents
        assert chunk in (512, 1024)


# -- the linear-attention family at its cell's shapes --------------------------

def lower_recurrent_program(topo, program):
    """``serve.solar-open2-250b.report-saturated``'s decode or prefill
    program, lowered from shapes as ``lower_serve_program`` does, at the
    cell's own slots, lengths and chunk."""
    from benchmark.harness import program as program_lib, spec
    from distributed_tensorflow_tpu.models import PagedKVConfig
    from distributed_tensorflow_tpu.models.solar_open2 import SolarOpen2
    from distributed_tensorflow_tpu.serve import sampling as sampling_lib
    from distributed_tensorflow_tpu.serve.engine import ServeEngine

    cell = spec.load_cell("serve.solar-open2-250b.report-saturated")
    sched = cell.cell["scheduler"]
    slots, total, block = (sched["num_slots"], sched["max_total_len"],
                           sched["block_size"])
    chunk, steps = sched["prefill_budget"], sched["megastep"]
    module = SolarOpen2(program_lib.program_config(cell.config))
    engine = object.__new__(ServeEngine)
    engine.module = module
    per_slot = total // block
    paged = PagedKVConfig(block_size=block, num_blocks=slots * per_slot + 1)

    def arg(shape, dtype=jnp.int32):
        return one_chip(topo, shape, dtype)

    variables = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((slots, total), jnp.int32),
        decode=True, slot_ids=jnp.arange(slots, dtype=jnp.int32),
        paged=paged, block_tables=jnp.zeros((slots, per_slot), jnp.int32)))
    params, cache = jax.tree.map(
        lambda s: arg(s.shape, s.dtype),
        (variables["params"], variables["cache"]))
    counts = arg((slots, module.cfg.vocab_size))
    tables = arg((slots, per_slot))
    rng = arg((), jax.random.key(0).dtype)
    sampling = lambda rows: jax.tree.map(
        lambda a: arg(np.shape(a), np.asarray(a).dtype),
        sampling_lib.uniform(rows, 0.0, 0))
    if program == "decode_megastep":
        fn = jax.jit(
            lambda *a: engine._megastep_counting_apply(steps, paged, *a),
            donate_argnums=(1, 2))
        lowered = fn.lower(
            params, cache, counts, arg((slots,)), arg((slots,), jnp.bool_),
            arg((slots,)), arg((slots,)), tables, rng, arg(()),
            sampling(slots), arg((slots,)), arg((slots,), jnp.bool_),
            arg(()))
    else:
        fn = jax.jit(
            lambda *a: engine._prefill_slots_apply(paged, *a),
            donate_argnums=(1, 2))
        lowered = fn.lower(
            params, cache, counts, arg((1, chunk)), arg((1,)), tables,
            rng, arg(()), arg((1,)), sampling(1), arg((1,), jnp.bool_))
    return lowered, cache, slots, chunk


@pytest.mark.parametrize("program", ["decode_megastep", "prefill_slots"])
def test_recurrent_serve_programs_fit_one_chip_at_the_cells_shapes(
        topo, program):
    """One chip's share of the v5e-128 deployment, published layers 0-3 at
    the published widths: 4.10 GB of bfloat16 weights, the GQA layer's pool
    of ``slots x 320 + 1`` blocks (4 KB a position) and the three linear
    layers' per-slot state, float32 ``(3, slots, 64, 128, 128)``, 12.6 MB
    a slot, with the convolution's tails beside it.

    The state is what a step OVERWRITES: the decode program (4 fused
    steps, each reading every slot's state and writing it back) may hold
    no second copy of it.  Its scratch stays under one layer's state and
    under the largest leaf of a layer (the ``qkv`` kernel, 201 MB): a state
    gated outside its update, gathered by slot, or a layer's leaves sliced
    out of the stack and copied a step (ROADMAP lesson (iv)) fails here,
    on the CPU.  A prefill chunk's scratch is the chunk-wise rule's
    operands for 1,024 positions (float32 ``(16, 64, 64, .)`` a tensor)
    and the chunk's float32 projections."""
    import time

    lowered, cache, slots, chunk = lower_recurrent_program(topo, program)
    pool = cache["full_pool"].shape
    assert pool == (1, slots * 320 + 1, 16, 2048)
    assert cache["kda_state"].shape == (3, slots, 64, 128, 128)
    assert cache["kda_state"].dtype == jnp.float32
    assert cache["kda_conv"].shape == (3, slots, 3, 24576)
    started = time.perf_counter()
    compiled = lowered.compile()
    assert time.perf_counter() - started < 240      # a cold start pays it
    memory = compiled.memory_analysis()
    layer_state = slots * 64 * 128 * 128 * 4
    held = (2 * np.prod(pool) + 3 * layer_state + 2 * 3 * slots * 3 * 24576
            + 4 * slots * 24576)
    assert 4.09e9 + held < memory.argument_size_in_bytes < 4.12e9 + held
    if program == "decode_megastep":
        assert memory.temp_size_in_bytes < min(layer_state, 0.2e9)
    else:
        assert memory.temp_size_in_bytes < 1.6e9
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < V5E_HBM_BYTES
    hlo = compiled.as_text()
    assert " scatter(" in hlo
    for name, op, line in pool_sized_results(hlo, pool):
        assert op in ("parameter", "get-tuple-element", "bitcast",
                      "scatter") or (
            op == "fusion" and " scatter(" in fused_computation(
                hlo, line)), f"%{name} is a pool-sized {op}"
    assert not kernel_calls(hlo)        # no flash kernel in a serving step
    shapes = {tuple(int(n) for n in dims.split(","))
              for dims in re.findall(r"= bf16\[([\d,]+)\]", hlo)}
    # No slot's whole table row of K/V gathered for a decode step (the
    # block-table kernel reads the pool where it lies); a chunk gathers its
    # one row.
    whole_rows = {(slots * 320, 16, 2048), (slots, 5120, 2048)}
    assert not shapes & whole_rows, shapes & whole_rows
    if program == "decode_megastep":
        assert "tpu_custom_call" in hlo
        assert (320, 16, 2048) not in shapes
    else:
        assert (320, 16, 2048) in shapes and chunk == 1024
