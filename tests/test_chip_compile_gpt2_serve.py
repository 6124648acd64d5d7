"""GPT-2 medium's serving programs at ``serve.gpt2-medium.chat-saturated``'s
shapes, compiled for a described TPU v5e: see ``test_chip_compile.py`` for
what a described compile is and why there are three files of them.
"""

import functools
import re

import numpy as np
import pytest

from tests.chip_compile_helpers import (  # noqa: F401 — fixtures by name
    V5E_HBM_BYTES, assert_only_scatters_produce_pools, lower_engine_program,
    pa, steer_to_the_kernel, topo)

# -- the paged KV pools are updated in place ----------------------------------

# serve.gpt2-medium.chat-saturated: slots x 1024 positions in blocks of 16,
# a full pool plus the trash block, 4 fused decode steps, one-slot prefills.
SERVE_TOTAL_LEN, SERVE_BLOCK, SERVE_MEGASTEP, SERVE_PROMPT = 1024, 16, 4, 128


def lower_serve_program(topo, program, slots):
    """GPT-2 medium's ``decode_megastep`` or ``prefill_slots`` program
    (``lower_engine_program``) and its key pool's shape.  The parameters are
    given in the types the engine holds them in, by the family's
    ``served_dtypes``."""
    from distributed_tensorflow_tpu.models import get_workload
    from distributed_tensorflow_tpu.models.gpt2 import (
        GPT2, GPT2Config, PagedKVConfig)

    module = GPT2(GPT2Config.medium(dropout=0.0))
    paged = PagedKVConfig(
        block_size=SERVE_BLOCK,
        num_blocks=slots * (SERVE_TOTAL_LEN // SERVE_BLOCK) + 1)
    lowered, cache = lower_engine_program(
        topo, module, paged, program, slots=slots, total=SERVE_TOTAL_LEN,
        steps=SERVE_MEGASTEP, prompt=SERVE_PROMPT,
        typed=get_workload("gpt2", config=module.cfg).served_dtypes)
    return lowered, cache["blocks"]["cached_key_pool"].shape


@functools.cache
def compiled_serve_program(topo, program, slots):
    """(compiled, pool): the tests that only read a program share its one
    compile (the 64-slot decode program has two)."""
    lowered, pool = lower_serve_program(topo, program, slots)
    return lowered.compile(), pool


@pytest.mark.parametrize("program", ["decode_megastep", "prefill_slots"])
def test_serve_program_updates_the_kv_pools_in_place(topo, program):
    """With the pools scanned over the layer axis, or stored with the head
    size of 64 in the minor dimension, each token step sliced, re-laid and
    re-stacked both pools: 11.5 GB of scratch for 1.6 GB of cache.  Carried
    through the layer loop and stored lane-dense, the only instructions
    that produce a pool are the scatters, on the program's own argument:
    no copy, no ``AllocateBuffer`` custom-call, no other fusion."""
    compiled, pool = compiled_serve_program(topo, program, 16)
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
    compiled, _ = compiled_serve_program(topo, "decode_megastep", 64)
    memory = compiled.memory_analysis()
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
    compiled, _ = compiled_serve_program(topo, program, 64)
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
