"""What the three described-compile files share (``test_chip_compile*.py``):
the described v5e, the steering fixture, and how a program is lowered for it
and read.  Imported by name, never collected: in ``conftest.py`` the autouse
``steer_to_the_kernel`` would steer every test of the suite.
"""

import importlib
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh

from distributed_tensorflow_tpu.cluster.topology import MESH_AXES

fa = importlib.import_module("distributed_tensorflow_tpu.ops.flash_attention")
pa = importlib.import_module("distributed_tensorflow_tpu.ops.paged_attention")

V5E_HBM_BYTES = 15.75e9
FLASH_KERNELS = {"flash_fwd", "flash_dq", "flash_dkv"}


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


def kernel_calls(hlo):
    """How many instructions carry each flash kernel's name: the profiler
    shows an executed instruction by this text, so a kernel's ``name=`` is
    how a device trace tells the three apart.  The compiler names the
    instruction after the innermost scope of its ``op_name``:
    ``%flash_fwd.3`` under a module's scope, ``%jvp_flash_fwd_.1`` bare."""
    calls = re.findall(
        r"%\w*?(flash_(?:fwd|dq|dkv))[\w.]* = [^\n]*tpu_custom_call", hlo)
    return {name: calls.count(name) for name in sorted(set(calls))}


def one_chip(topo, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(
        shape, dtype,
        sharding=jax.sharding.SingleDeviceSharding(topo.devices[0]))


# -- the server's programs ----------------------------------------------------

def lower_engine_program(topo, module, paged, program, *, slots, total,
                         steps, prompt, typed=None):
    """The engine's own ``decode_megastep`` (``steps`` fused) or
    ``prefill_slots`` (one slot, ``prompt`` positions) program for
    ``module``, lowered from shapes alone: (lowered, the cache's shapes).
    ``ServeEngine()`` places real weights, which a described device cannot
    hold, so the two ``_apply`` methods run on a bare instance that has only
    the module they read.  The parameters are given as declared, or in the
    types ``typed`` makes of them (a family's ``served_dtypes``)."""
    from distributed_tensorflow_tpu.serve import engine as engine_lib
    from distributed_tensorflow_tpu.serve import sampling as sampling_lib

    engine = object.__new__(engine_lib.ServeEngine)
    engine.module = module
    width = paged.table_width(total)

    def arg(shape, dtype=jnp.int32):
        return one_chip(topo, shape, dtype)

    variables = jax.eval_shape(lambda: module.init(
        jax.random.key(0), jnp.zeros((slots, total), jnp.int32),
        decode=True, slot_ids=jnp.arange(slots, dtype=jnp.int32),
        paged=paged, block_tables=jnp.zeros((slots, width), jnp.int32)))
    declared = jax.tree.map(lambda s: s.dtype, variables["params"])
    params, cache = jax.tree.map(
        lambda s, dtype: arg(s.shape, dtype),
        (variables["params"], variables["cache"]),
        (typed(variables["params"]) if typed else declared,
         jax.tree.map(lambda s: s.dtype, variables["cache"])))
    counts = arg((slots, module.cfg.vocab_size))
    tables = arg((slots, width))
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
            lambda *a: apply(steps, paged, *a), donate_argnums=(1, 2))
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
    return lowered, cache


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


def assert_only_scatters_produce_pools(hlo, pool, flattened=False):
    """In place: nothing but a scatter makes a pool-sized array.
    ``flattened``: the program's scatters are written over the pool's rows
    flattened and show here as a bitcast of their fusion, so none of them
    has a pool's shape itself."""
    produced = pool_sized_results(hlo, pool)
    assert (" scatter(" in hlo if flattened
            else any(op == "scatter" for _, op, _ in produced))
    for name, op, line in produced:
        if op == "fusion":
            assert " scatter(" in fused_computation(hlo, line), (
                f"%{name} makes a pool-sized array and is no scatter")
        else:
            assert op in ("parameter", "get-tuple-element", "scatter",
                          "bitcast"), f"%{name} is a pool-sized {op}"
