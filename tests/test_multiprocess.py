"""Tier-(c) distributed tests (SURVEY.md §5): REAL multi-process cluster on
localhost — the JAX analog of TF's create_in_process_cluster/
MultiProcessRunner tests.  Two controller processes, TF_CONFIG contract,
jax.distributed coordination, cross-process collective, and the
collective-mismatch guard.
"""

import os
import socket
import subprocess
import sys

import pytest

from tests.helpers import free_ports

WORKER_SCRIPT = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

from distributed_tensorflow_tpu import cluster as cluster_lib

resolver = cluster_lib.resolve()
server = cluster_lib.Server.from_resolver(resolver)
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 4, jax.device_count()

# cross-process host allgather
from jax.experimental import multihost_utils
vals = multihost_utils.process_allgather(
    np.asarray([jax.process_index() + 1], np.int32)
)
assert int(np.asarray(vals).sum()) == 3, vals

# collective-mismatch guard agrees on identical programs
cluster_lib.assert_same_program("mp_test", {"shape": (4, 4)})

# global-mesh computation: one sharded array over 4 devices, global sum
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = cluster_lib.build_mesh(cluster_lib.MeshConfig(data=4))
sh = NamedSharding(mesh, P("data"))
local = np.arange(2, dtype=np.float32) + 2 * jax.process_index()
garr = jax.make_array_from_process_local_data(sh, local)
total = jax.jit(lambda a: a.sum(), out_shardings=NamedSharding(mesh, P()))(garr)
assert float(total) == 0 + 1 + 2 + 3, float(total)

server.shutdown()
print("MP_OK", jax.process_index())
"""



HEALTH_SCRIPT = r"""
import os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp

from distributed_tensorflow_tpu import cluster as cluster_lib
from distributed_tensorflow_tpu.ft import HealthChecker

resolver = cluster_lib.resolve()
server = cluster_lib.Server.from_resolver(resolver)
assert jax.process_count() == 2

# BOTH processes run checkers (probes are barriers — they need every live
# peer participating).  Sync the start so the first probe boundary finds
# both checkers running, making the healthy phase deterministic.
cluster_lib.barrier("health_test_start")
checker = HealthChecker(interval_s=2.0, timeout_s=1.5,
                        failures_before_action=2).start()

if jax.process_index() == 1:
    # the doomed peer: probe healthily for ~3 intervals, then die without
    # cleanup mid-run
    time.sleep(6.5)
    os._exit(1)

# survivor (process 0 = coordinator): a training-like loop with the health
# checker.  Phase 1: peer alive -> probes must SUCCEED (a probe that
# reports unhealthy on a healthy cluster would kill real training runs).
step = jax.jit(lambda x: x + 1)
x = jnp.zeros(())
t0 = time.time()
while time.time() - t0 < 5.5:
    x = step(x)
    checker.raise_if_unhealthy()   # raises -> healthy-phase failure
    time.sleep(0.1)
print("HEALTH_PHASE1_OK", flush=True)

# Phase 2: peer dies at ~6.5s -> a dead peer must surface as a raise within
# ~2 probe intervals, not a hang.
deadline = time.time() + 60
try:
    while time.time() < deadline:
        x = step(x)
        checker.raise_if_unhealthy()
        time.sleep(0.1)
    print("HEALTH_TIMEOUT")  # checker never tripped: test failure
except RuntimeError as e:
    assert "unhealthy" in str(e), e
    checker.stop()
    print("HEALTH_RAISED", flush=True)
    # Skip the atexit jax.distributed shutdown: its cluster-wide shutdown
    # barrier can only fail against the dead peer and would turn this
    # deliberate fail-fast into a noisy crash.
    os._exit(0)
finally:
    checker.stop()
"""


def test_health_checker_detects_dead_peer(tmp_path):
    """Killing one worker makes the survivor raise within ~2 probe
    intervals (VERDICT weak #5 / SURVEY §6.3 MWMS check-health)."""
    import json

    p0, p1 = free_ports(2)
    cluster = {"worker": [f"localhost:{p0}", f"localhost:{p1}"]}
    procs = []
    for idx in range(2):
        env = dict(
            os.environ,
            TF_CONFIG=json.dumps(
                {"cluster": cluster, "task": {"type": "worker", "index": idx}}
            ),
            JAX_PLATFORMS="cpu",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", HEALTH_SCRIPT],
                env=env,
                cwd=os.path.dirname(os.path.dirname(__file__)),
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    try:
        out0, _ = procs[0].communicate(timeout=180)
    except subprocess.TimeoutExpired:
        for q in procs:
            q.kill()
        pytest.fail("survivor hung instead of failing fast")
    procs[1].wait(timeout=30)
    assert "HEALTH_PHASE1_OK" in out0, out0[-4000:]  # healthy phase exercised
    assert "HEALTH_RAISED" in out0, out0[-4000:]
    assert procs[0].returncode == 0, out0[-4000:]


TRAIN_SCRIPT = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

from distributed_tensorflow_tpu.train_lib import TrainArgs, run

result = run(TrainArgs(model="mnist", steps=6, batch_size=64, log_every=3))
assert result["final_step"] == 6, result
assert np.isfinite(result["loss"]), result
print("TRAIN_OK", jax.process_index(), flush=True)
from tests.helpers import leave_in_order
leave_in_order()
"""


def test_two_process_train_lib_run(tmp_path):
    """The FULL entrypoint (train_lib.run) on a real 2-worker cluster.

    Regression test for two bugs only this path could expose: the
    collective-mismatch fingerprint embedding per-process memory
    addresses (guard tripped on identical programs), and HealthCheckHook
    probing before the peer finished compiling (healthy run killed).
    DTT_HEALTH_INTERVAL_S=5 makes probes actually fire during the run —
    with 1-core serialized 30-60s compiles the unarmed checker would trip
    within ~10s while the peer is still compiling, while the armed one
    keeps a 3.75s barrier timeout that tolerates test-host load."""
    from tests.helpers import join_workers, spawn_worker_cluster

    procs = spawn_worker_cluster(
        TRAIN_SCRIPT, 2, extra_env={"DTT_HEALTH_INTERVAL_S": "5"}
    )
    outs = join_workers(procs, timeout=420, fail=pytest.fail)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i}:\n{out[-4000:]}"
        assert f"TRAIN_OK {i}" in out, out[-2000:]


HYBRID_SCRIPT = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

from distributed_tensorflow_tpu import cluster as cluster_lib
from distributed_tensorflow_tpu.data import per_host_batch_size
from distributed_tensorflow_tpu.data.pipeline import make_global_batches
from distributed_tensorflow_tpu.models import get_workload
from distributed_tensorflow_tpu.models.gpt2 import GPT2Config
from distributed_tensorflow_tpu.train_lib import build_state_and_step
from distributed_tensorflow_tpu.training import FP32

resolver = cluster_lib.resolve()
server = cluster_lib.Server.from_resolver(resolver)
assert jax.process_count() == 2 and jax.device_count() == 8

cfg = cluster_lib.MeshConfig(data=2, fsdp=2, tensor=2)
mesh = cluster_lib.build_hybrid_mesh(cfg)
# DCN granule = process: each process's 4 local devices form one
# "slice" holding fsdp=2 x tensor=2; the data axis crosses processes.
assert dict(mesh.shape)["data"] == 2
local0 = {d.process_index for d in mesh.devices[0].ravel()}
local1 = {d.process_index for d in mesh.devices[1].ravel()}
assert local0 != local1 and len(local0) == len(local1) == 1, (
    "each data slice must live entirely inside one process")


def run3(mesh):
    wl = get_workload("gpt2", config=GPT2Config.tiny(), batch_size=8,
                      seq_len=32, grad_accum_steps=1, mesh=mesh)
    state, _, step, batch_sh = build_state_and_step(
        wl, mesh, precision=FP32, total_steps=5)
    data = make_global_batches(
        wl.data_fn(per_host_batch_size(wl.batch_size)),
        batch_sh[wl.example_key])
    losses = []
    rng = jax.random.key(1)
    for i, batch in zip(range(3), data):
        state, m = step(state, batch, jax.random.fold_in(rng, i))
        losses.append(float(m["loss"]))
    return state, losses

state_h, losses_h = run3(mesh)
state_f, losses_f = run3(cluster_lib.build_mesh(cfg))
# Gradient agreement: the hybrid (DCN data axis) layout must train
# identically to the flat mesh — same data, same init, same losses.
np.testing.assert_allclose(losses_h, losses_f, rtol=1e-4)

# Cross-process agreement: every process sees the same updated params.
from jax.experimental import multihost_utils
probe = np.asarray(jax.device_get(
    jax.jit(lambda s: s.params["wte"].astype(np.float32).sum())(state_h)))
gathered = np.asarray(multihost_utils.process_allgather(probe))
assert np.allclose(gathered, gathered[0]), gathered

server.shutdown()
print("HYBRID_OK", jax.process_index(), losses_h, flush=True)
from tests.helpers import leave_in_order
leave_in_order()
"""


def test_two_process_hybrid_dcn_mesh_training(tmp_path):
    """VERDICT r2 missing #4: real train steps on 2 processes x 4 devices
    with build_hybrid_mesh — DCN `data` axis across processes, ICI
    fsdp/tensor axes inside each — asserting cross-process gradient
    agreement (loss parity with the flat mesh + identical params on every
    process)."""
    from tests.helpers import join_workers, spawn_worker_cluster

    procs = spawn_worker_cluster(HYBRID_SCRIPT, 2)
    outs = join_workers(procs, timeout=420, fail=pytest.fail)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i}:\n{out[-4000:]}"
        assert f"HYBRID_OK {i}" in out, out[-2000:]


PIPE_SCRIPT = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from jax.sharding import AxisType, Mesh

from distributed_tensorflow_tpu import cluster as cluster_lib
from distributed_tensorflow_tpu.models import get_workload
from distributed_tensorflow_tpu.models.gpt2 import GPT2Config

resolver = cluster_lib.resolve()
server = cluster_lib.Server.from_resolver(resolver)
assert jax.process_count() == 2 and jax.device_count() == 8

# Manual mesh with `pipe` as the SLOWEST axis: pipe rank 0 = process 0's
# devices, pipe rank 1 = process 1's — every pipeline stage hand-off
# (ppermute over `pipe`) crosses the process boundary for real.
dev = np.array(jax.devices()).reshape(2, 1, 1, 1, 1, 4)
axes = ("pipe", "fsdp", "tensor", "context", "expert", "data")
mesh = Mesh(dev, axes, axis_types=(AxisType.Auto,) * 6)
for k in range(2):
    owners = {d.process_index for d in dev[k].ravel()}
    assert owners == {k}, (k, owners)


from tests.helpers import stream_fed_losses


def run2(schedule):
    wl = get_workload(
        "gpt2", config=GPT2Config.tiny(), batch_size=8, seq_len=32,
        grad_accum_steps=1, mesh=mesh, pipe_schedule=schedule,
    )
    return stream_fed_losses(wl, mesh)


losses_gpipe = run2("gpipe")
losses_1f1b = run2("1f1b")
assert np.isfinite(losses_gpipe).all() and np.isfinite(losses_1f1b).all()
# Same math, different schedule — across a REAL process boundary.
np.testing.assert_allclose(losses_gpipe, losses_1f1b, rtol=1e-4)

server.shutdown()
print("PIPE_MP_OK", jax.process_index(), losses_1f1b, flush=True)
from tests.helpers import leave_in_order
leave_in_order()
"""


def test_two_process_pipeline_pipe_axis(tmp_path):
    """Pipeline tier-c: the `pipe` axis spans 2 processes (every GPipe/1F1B
    stage hand-off ppermute crosses the process boundary); both schedules
    train GPT-2 with matching losses."""
    from tests.helpers import join_workers, spawn_worker_cluster

    procs = spawn_worker_cluster(PIPE_SCRIPT, 2)
    outs = join_workers(procs, timeout=420, fail=pytest.fail)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i}:\n{out[-4000:]}"
        assert f"PIPE_MP_OK {i}" in out, out[-2000:]


RING_SCRIPT = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

from distributed_tensorflow_tpu import cluster as cluster_lib
from distributed_tensorflow_tpu.models import get_workload
from distributed_tensorflow_tpu.models.bert import BertConfig

resolver = cluster_lib.resolve()
server = cluster_lib.Server.from_resolver(resolver)
assert jax.process_count() == 2 and jax.device_count() == 8

# context=8 spans BOTH processes: the ring's ppermute crosses the process
# boundary every step — KV blocks transit the DCN-like hop for real.
ring_mesh = cluster_lib.build_mesh(cluster_lib.MeshConfig(data=1, context=8))
owners = [d.process_index for d in ring_mesh.devices.ravel()]
assert len(set(owners)) == 2, owners


from tests.helpers import stream_fed_losses


def run2(mesh):
    wl = get_workload("bert", config=BertConfig.tiny(dtype=np.float32),
                      batch_size=8, seq_len=64, mesh=mesh)
    return stream_fed_losses(wl, mesh)

losses_ring = run2(ring_mesh)
losses_flat = run2(cluster_lib.build_mesh(cluster_lib.MeshConfig(data=8)))
# Exact attention: the cross-process ring must train identically to the
# flat DP mesh (same data, same init).
np.testing.assert_allclose(losses_ring, losses_flat, rtol=1e-4)

server.shutdown()
print("RING_MP_OK", jax.process_index(), losses_ring, flush=True)
from tests.helpers import leave_in_order
leave_in_order()
"""


def test_two_process_ring_attention_context_axis(tmp_path):
    """Long-context tier-c: BERT's non-causal ring attention with the
    `context` axis spanning 2 processes — every ppermute KV rotation
    crosses the process boundary — matches the flat-DP loss exactly."""
    from tests.helpers import join_workers, spawn_worker_cluster

    procs = spawn_worker_cluster(RING_SCRIPT, 2)
    outs = join_workers(procs, timeout=420, fail=pytest.fail)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i}:\n{out[-4000:]}"
        assert f"RING_MP_OK {i}" in out, out[-2000:]


def test_two_process_localhost_cluster(tmp_path):
    import json

    p0, p1 = free_ports(2)
    cluster = {"worker": [f"localhost:{p0}", f"localhost:{p1}"]}
    procs = []
    for idx in range(2):
        env = dict(
            os.environ,
            TF_CONFIG=json.dumps(
                {"cluster": cluster, "task": {"type": "worker", "index": idx}}
            ),
            JAX_PLATFORMS="cpu",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", WORKER_SCRIPT],
                env=env,
                cwd=os.path.dirname(os.path.dirname(__file__)),
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process workers hung")
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out[-4000:]}"
        assert f"MP_OK {i}" in out, out[-2000:]


FILESET_TRAIN_SCRIPT = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

from distributed_tensorflow_tpu.train_lib import TrainArgs, run

data_dir = os.environ["DTT_TEST_FILESET_DIR"]
result = run(TrainArgs(model="mnist", steps=4, batch_size=32, log_every=2,
                       data_dir=data_dir, auto_shard_policy="file"))
assert result["final_step"] == 4, result
assert np.isfinite(result["loss"]), result
print("FILESET_TRAIN_OK", jax.process_index(), flush=True)
from tests.helpers import leave_in_order
leave_in_order()
"""


def test_two_process_file_sharded_fileset_training(tmp_path):
    """VERDICT r3 #4 tier-c: a 4-file fileset trains across 2 REAL
    processes under FILE auto-shard — each host reads only its own file
    group (files i % 2), through the full train_lib entrypoint."""
    from distributed_tensorflow_tpu.data.records import (
        stage_synthetic_to_records,
    )
    from distributed_tensorflow_tpu.models import get_workload
    from tests.helpers import join_workers, spawn_worker_cluster

    wl = get_workload("mnist", batch_size=32)
    stage_synthetic_to_records(
        wl, str(tmp_path / "mnist.rec"), 128, chunk=32, num_files=4)
    procs = spawn_worker_cluster(
        FILESET_TRAIN_SCRIPT, 2,
        extra_env={"DTT_TEST_FILESET_DIR": str(tmp_path),
                   "DTT_HEALTH_INTERVAL_S": "5"},
    )
    outs = join_workers(procs, timeout=420, fail=pytest.fail)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i}:\n{out[-4000:]}"
        assert f"FILESET_TRAIN_OK {i}" in out, out[-2000:]
