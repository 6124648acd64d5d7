"""Two trainer processes sharing ONE data service — the full
tf.data-service topology (SURVEY.md §3.4): a 2-worker jax.distributed
cluster where both workers pull disjoint batches from a single input
server instead of striping the record file.
"""

import os
import select
import subprocess
import sys

import pytest

from tests.helpers import REPO, join_workers, spawn_worker_cluster

TRAINER_SCRIPT = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

from distributed_tensorflow_tpu.train_lib import TrainArgs, run

result = run(TrainArgs(
    model="mnist", steps=6, batch_size=64, log_every=3,
    data_service=sys.argv[1],
))
assert result["final_step"] == 6, result
assert np.isfinite(result["loss"]), result
print("TRAINER_OK", jax.process_index(), flush=True)
from tests.helpers import leave_in_order
leave_in_order()
"""


def test_two_trainers_one_data_service(tmp_path):
    from distributed_tensorflow_tpu.data.records import (
        record_path,
        stage_synthetic_to_records,
    )
    from distributed_tensorflow_tpu.models import get_workload

    wl = get_workload("mnist", batch_size=64)
    stage_synthetic_to_records(
        wl, record_path(str(tmp_path), "mnist"), 512
    )
    svc_env = dict(os.environ, JAX_PLATFORMS="cpu")
    # per-host batch for a 2-worker cluster with global batch 64 is 32
    service = subprocess.Popen(
        [sys.executable, "-m", "distributed_tensorflow_tpu.data.service",
         "--model=mnist", f"--data_dir={tmp_path}", "--batch_size=32"],
        env=svc_env, cwd=REPO, stdout=subprocess.PIPE, text=True,
    )
    try:
        ready, _, _ = select.select([service.stdout], [], [], 120)
        if not ready:
            pytest.fail("data service never became ready")
        line = service.stdout.readline()
        assert line.startswith("DATA_SERVICE_READY"), line
        target = line.split()[1]

        trainers = spawn_worker_cluster(TRAINER_SCRIPT, 2, args=(target,))
        outs = join_workers(trainers, timeout=300, fail=pytest.fail)
        for i, (p, out) in enumerate(zip(trainers, outs)):
            assert p.returncode == 0, f"trainer {i}:\n{out[-4000:]}"
            assert f"TRAINER_OK {i}" in out, out[-2000:]
    finally:
        service.terminate()
        try:
            service.wait(timeout=30)
        except subprocess.TimeoutExpired:
            service.kill()
            service.wait(timeout=10)
