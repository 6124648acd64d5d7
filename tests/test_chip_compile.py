"""One chip's programs at the cells' own shapes, compiled for a TPU v5e that
is described, not attached (on-chip-measurement guide, section 2): the three
flash kernels at the training cells' shapes, and the four sparse-expert
families' serving programs at their cells'.

Every other kernel test runs in the Pallas interpreter, which cannot see what
the chip's compiler refuses: a block that does not tile, too much VMEM, a
Mosaic kernel handed to the automatic partitioner.  These cases ask the real
compiler, at real widths, and use no chip time: a kernel alone takes 1-3
seconds, a whole program 25-57 alone and 30-110 under the suite's six workers.
Nothing runs, so nothing here says anything about results or speed.

The described compiles are three files, so that ``--dist loadfile`` gives
them to three workers: this one, ``test_chip_compile_train.py`` (layers and
whole training programs on the described 2x2) and
``test_chip_compile_gpt2_serve.py`` (GPT-2's serving programs);
``chip_compile_helpers.py`` is what they share.  xdist hands files out by
their number of tests, largest first, and hands a worker its next file when
two tests of its last are left: a file of few long tests is the run's tail,
and a file that ends on long tests keeps the next one waiting.  So the
kernels' many short cases live here, after the families' eight long ones:
they start this file by the middle of the run and let it end on short tests
(ROADMAP D8).  Each process loads the compile-only libtpu once, and
``ALLOW_MULTIPLE_LIBTPU_LOAD`` lets them load it side by side (no chip is
held, so the multi-process lock protects nothing).
"""

import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.chip_compile_helpers import (  # noqa: F401 — fixtures by name
    FLASH_KERNELS, V5E_HBM_BYTES, assert_only_scatters_produce_pools,
    compiled_text, fa, kernel_calls, lower_engine_program, one_chip,
    steer_to_the_kernel, topo)

# -- the families' serving programs ------------------------------------------

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


def lower_cell_program(topo, name, program, whole_prompt=False):
    """The decode or prefill program of serving cell ``name``, lowered from
    shapes (``lower_engine_program``) at the cell's own slots, lengths and
    fused steps, with the pools sized as its scheduler sizes them (a family
    with window layers: a second pool in which a slot owns a ring, and two
    tables a slot).  A prefill launch is one chunk of the cell's
    ``prefill_budget`` or, with ``whole_prompt``, the traffic's longest
    prompt.  -> (lowered, the cache's shapes, slots, the launch's positions)"""
    from benchmark.harness import program as program_lib, spec
    from distributed_tensorflow_tpu.models import PagedKVConfig, get_workload

    cell = spec.load_cell(name)
    sched = cell.cell["scheduler"]
    slots, total, block = (sched["num_slots"], sched["max_total_len"],
                           sched["block_size"])
    chunk, steps = sched["prefill_budget"], sched["megastep"]
    workload = get_workload(cell.config["program"]["model"],
                            config=program_lib.program_config(cell.config))
    pool = dict(block_size=block, num_blocks=slots * (total // block) + 1)
    window = workload.cache_geometry(PagedKVConfig(**pool)).get(
        "window_positions", 0)
    if window:
        ring = -(-(window + chunk + steps) // block) + 1
        pool.update(window_blocks=slots * ring + 1, window_ring=ring)
    if whole_prompt:
        chunk = max(cell.traffic["prompt_tokens"]["round_up_to"])
    lowered, cache = lower_engine_program(
        topo, workload.module, PagedKVConfig(**pool), program, slots=slots,
        total=total, steps=steps, prompt=chunk)
    return lowered, cache, slots, chunk


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


# -- the latent-attention, sparse-expert family at its cell's shapes ----------

@pytest.mark.parametrize("program", ["decode_megastep", "prefill_slots"])
def test_latent_serve_programs_fit_one_chip_at_the_cells_shapes(topo, program):
    """One chip's share of the expert-parallel deployment, 1 dense + 20
    expert layers at the published widths: 4.60 GB of bfloat16 weights and
    a latent pool of 16 slots x 1,024 positions x 21 layers x 640 values
    (0.44 GB), updated in place; the decode program (4 fused steps, with
    the router's counts as one more output) adds 0.43 GB of scratch, the
    longest prefill (384 positions) 0.04 GB.  PERF.md section 4 quotes
    these figures."""
    lowered, cache, slots, longest = lower_cell_program(
        topo, "serve.glm-4.7-flash.reason-saturated", program,
        whole_prompt=True)
    pool = cache["latent_pool"].shape
    assert pool == (21, slots * 64 + 1, 16, 640) and longest == 384
    compiled = lowered.compile()
    assert_each_assignment_once(compiled, GLM_47, program)
    memory = compiled.memory_analysis()
    assert 5.0e9 < memory.argument_size_in_bytes < 5.1e9
    assert memory.temp_size_in_bytes < (0.5e9 if program == "decode_megastep"
                                        else 0.06e9)
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < V5E_HBM_BYTES
    assert_only_scatters_produce_pools(compiled.as_text(), pool)


# -- the grouped-query, window-and-full family at its cell's shapes -----------

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
    lowered, cache, slots, _ = lower_cell_program(
        topo, "serve.mellum2-12b-a2.5b.code-mixed-saturated", program)
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
    lowered, cache, slots, chunk = lower_cell_program(
        topo, "serve.glm-5.2.longdoc-saturated", program)
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
    for pool in (latent, index):
        assert_only_scatters_produce_pools(hlo, pool, flattened=True)
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


# -- the window-latent family at its cell's shapes -----------------------------

@pytest.mark.parametrize("program", ["decode_megastep", "prefill_slots"])
def test_window_latent_serve_programs_fit_one_chip_at_the_cells_shapes(
        topo, program):
    """One chip's share of the v5e-256 deployment, published layers 0-4 at
    the published widths: 3.64 GB of bfloat16 weights; the two full layers'
    latent pool of ``slots x 512 + 1`` blocks and their index keys under the
    same block numbers; the three window layers' ring pool of ``slots x 98 +
    1`` blocks, 1,152 wide; all three updated in place.  The assertion's
    message carries the argument and the scratch bytes (the cell's
    ``num_slots_arithmetic`` quotes them).

    A decode step gathers the selected rows and the ring blocks that hold
    the window (34 of 16 positions) and no table row; a prefill chunk the
    97 ring blocks of itself and the window before it."""
    lowered, cache, slots, chunk = lower_cell_program(
        topo, "serve.dots3-note-prev.notes-mixed-saturated", program)
    latent, index = cache["latent_pool"].shape, cache["index_pool"].shape
    window = cache["window_pool"].shape
    assert latent == (2, slots * 512 + 1, 16, 640)
    assert index == (2, slots * 512 + 1, 16, 128)
    assert window == (3, slots * 98 + 1, 16, 1152)
    started = time.perf_counter()
    compiled = lowered.compile()
    seconds = time.perf_counter() - started
    memory = compiled.memory_analysis()
    said = (f"{program}: {slots} slots, arguments "
            f"{memory.argument_size_in_bytes:,} B, scratch "
            f"{memory.temp_size_in_bytes:,} B, compiled in {seconds:.0f} s")
    print(said)
    assert seconds < 240, said                      # a cold start pays it
    pools = 2 * (np.prod(latent) + np.prod(index) + np.prod(window))
    assert (3.64e9 + pools < memory.argument_size_in_bytes
            < 3.66e9 + pools), said
    assert memory.temp_size_in_bytes < 1.2e9, said
    assert (memory.argument_size_in_bytes
            + memory.temp_size_in_bytes) < V5E_HBM_BYTES, said
    hlo = compiled.as_text()
    for kernel in ("expert_gate_up", "expert_down"):
        calls = re.findall(rf"%({kernel}[\w.]*) = [^\n]*tpu_custom_call", hlo)
        assert len(calls) == 4, (kernel, calls)
    for pool in (latent, index, window):
        assert_only_scatters_produce_pools(hlo, pool, flattened=True)
    assert not kernel_calls(hlo)        # no flash kernel in a serving step
    shapes = {tuple(int(n) for n in dims.split(","))
              for dims in re.findall(r"= bf16\[([\d,]+)\]", hlo)}
    whole_rows = {(slots * 512, 16, 640), (slots, 8192, 640),
                  (slots * 512, 16, 128), (slots, 8192, 128),
                  (512, 16, 640), (1, 8192, 640), (1, 8192, 128),
                  (slots * 98, 16, 1152), (slots, 1568, 1152)}
    assert not shapes & whole_rows, (shapes & whole_rows, said)
    if program == "decode_megastep":
        assert (slots, 2048, 640) in shapes, said   # the selected rows
        assert {(slots, 34, 16, 1152), (slots, 544, 1152)} & shapes, said
    else:
        assert chunk == 1024
        assert {(1, 97, 16, 1152), (1, 1552, 1152)} & shapes, said


# -- the linear-attention family at its cell's shapes --------------------------

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
    lowered, cache, slots, chunk = lower_cell_program(
        topo, "serve.solar-open2-250b.report-saturated", program)
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
    assert_only_scatters_produce_pools(hlo, pool, flattened=True)
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


# -- the flash kernels alone ---------------------------------------------------

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
    assert set(kernel_calls(hlo)) == (
        FLASH_KERNELS if backward else {"flash_fwd"})


def test_ring_block_kernel_with_lse_cotangent_compiles_for_v5e(topo):
    """What ring attention consumes per kv block: (out, lse), both with a
    cotangent, so the backward kernels take the g_lse operand."""
    qkv = one_chip(topo, RING_BLOCK)

    def block(q, k, v):
        out, lse = fa.flash_attention_with_lse(q, k, v, causal=False)
        return out.astype(jnp.float32).sum() + lse.sum()

    hlo = compiled_text(jax.grad(block, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert hlo.count("tpu_custom_call") >= 3
