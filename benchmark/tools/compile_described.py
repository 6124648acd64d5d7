"""Third rehearsal: compile a training cell's real step for a described
(not attached) ``v5e:2x2`` topology, here on the CPU, and print what the
TPU compiler says: bytes per device, Pallas calls, collectives.  Nothing
runs; a compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_described.py --workload <cell>
"""

import argparse
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    from jax.experimental import topologies

    from benchmark.harness import spec, train
    from distributed_tensorflow_tpu.ops import flash_attention as fa

    cell = spec.load_cell(args.workload)
    if cell.cell["kind"] != "train":
        sys.exit("only training cells build their step from shapes alone")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices)[:cell.chips]
    # The kernel module asks jax.devices() for its platform and would take
    # its CPU branch here: steer it, as tests/test_chip_compile.py does.
    fa._platform = lambda: "tpu"
    os.environ.pop("DTT_PALLAS_INTERPRET", None)

    workload, _, abstract, shardings, train_step, batch_sh = train.build_step(
        cell, devices)
    mesh = next(iter(batch_sh.values())).mesh
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        abstract, shardings)
    rows = int(cell.traffic["batch_size"])
    batch = {k: jax.ShapeDtypeStruct((rows,) + v.shape[1:], v.dtype,
                                     sharding=batch_sh[k])
             for k, v in workload.init_batch.items()}
    rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=NamedSharding(mesh, P()))
    t0 = time.perf_counter()
    compiled = train_step.lower(state, batch, rng).compile()
    hlo = compiled.as_text()
    print("compile seconds", round(time.perf_counter() - t0, 1))
    print("memory per device", compiled.memory_analysis())
    print("tpu_custom_call", hlo.count("tpu_custom_call"))
    print({name: len(re.findall(rf"\b{name}(?:-start)?\(", hlo))
           for name in ("all-reduce", "all-gather", "reduce-scatter",
                        "collective-permute", "all-to-all")})


if __name__ == "__main__":
    main()
