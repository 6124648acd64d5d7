"""Records the small profiler traces kept under ``benchmark/tests/data``:
a few steps of a toy training step that calls the flash attention kernels
inside a layer scan (one chip), and, where four chips are attached, of the
same step over ``data=2 x tensor=2`` (collectives).  Run on the chip; the
files land in ``chiprun_out/test_trace/``.

    python3 benchmark/tools/record_test_trace.py
"""

import glob
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402

OUT = os.path.join("chiprun_out", "test_trace")


def record(tag, axes, devices):
    from benchmark.harness.spans import Spans
    from distributed_tensorflow_tpu import cluster as cluster_lib
    from distributed_tensorflow_tpu import train_lib
    from distributed_tensorflow_tpu.data.pipeline import make_global_batches
    from distributed_tensorflow_tpu.models import get_workload
    from distributed_tensorflow_tpu.models.gpt2 import GPT2Config
    from distributed_tensorflow_tpu.training import BF16, TrainLoop

    mesh = cluster_lib.build_mesh(cluster_lib.MeshConfig(**axes), devices)
    cfg = GPT2Config(vocab_size=512, n_positions=256, d_model=256, n_layer=2,
                     n_head=4, dropout=0.0)
    wl = get_workload("gpt2", mesh=mesh, config=cfg, batch_size=8,
                      seq_len=256, grad_accum_steps=2,
                      use_flash_attention=True)
    init, _, _, step, bsh = train_lib.build_step(
        wl, mesh, precision=BF16, grad_accum_steps=2, total_steps=100)
    spans = Spans()

    def batches():
        it = make_global_batches(wl.data_fn(8), bsh[wl.example_key])
        while True:
            with spans.span("next_batch"):
                b = next(it)
            yield b

    loop = TrainLoop(step, init(), batches(), examples_per_step=8,
                     metrics_every=1, rng=jax.random.key(1))
    jax.block_until_ready(loop.run(2))
    d = os.path.join(OUT, f"dir_{tag}")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    with spans.span("window"):
        jax.block_until_ready(loop.run(3))
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        d, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(path, os.path.join(OUT, f"{tag}.xplane.pb"))
    shutil.rmtree(d)
    print(tag, os.path.getsize(os.path.join(OUT, f"{tag}.xplane.pb")), "bytes")


if __name__ == "__main__":
    os.makedirs(OUT, exist_ok=True)
    devices = jax.devices()
    record("toy_train_1chip", {}, devices[:1])
    if len(devices) >= 4:
        record("toy_train_d2t2", {"data": 2, "tensor": 2}, devices[:4])
