"""End-to-end tests for the reference-idiom example launchers.

Closes VERDICT weak #6: ``compat.v1``'s "train.py runs unchanged" claim is
demonstrated by *executing* a TF1-style PS launcher script (ClusterSpec +
Server + replica_device_setter + MonitoredTrainingSession +
SyncReplicasOptimizer), not just checking call shapes.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

from tests.helpers import free_ports

REPO = os.path.dirname(os.path.dirname(__file__))
LAUNCHER = os.path.join(REPO, "examples", "tf1_ps_launcher.py")



def _env():
    return dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )


def test_tf1_ps_launcher_single_process(tmp_path):
    """The reference's local-run mode: one process, trains BERT-tiny end to
    end through every TF1 shim, checkpoints, and reports a finite loss."""
    ckpt = tmp_path / "ckpt"
    out = subprocess.run(
        [
            sys.executable, LAUNCHER,
            "--train_steps", "8", "--batch_size", "8", "--seq_len", "32",
            "--sync_replicas", "2", "--log_every", "2",
            "--checkpoint_dir", str(ckpt),
        ],
        env=_env(), cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "TF1_PS_LAUNCHER_DONE" in out.stdout, out.stdout[-2000:]
    line = [l for l in out.stdout.splitlines() if "TF1_PS_LAUNCHER_DONE" in l][0]
    loss = float(line.split("loss=")[1])
    assert loss == loss and loss > 0  # finite, nonzero
    # chief-only MonitoredTrainingSession checkpointing really saved
    assert any(ckpt.iterdir()), "no checkpoint written"


def test_tf1_ps_launcher_ps_and_worker(tmp_path):
    """Reference cluster mode: a real ps process parks in Server.join() while
    the worker trains; worker completion terminates the ps (launcher
    contract, SURVEY.md §4.2)."""
    ps_port, w_port = free_ports(2)
    common = [
        "--ps_hosts", f"localhost:{ps_port}",
        "--worker_hosts", f"localhost:{w_port}",
        "--train_steps", "4", "--batch_size", "8", "--seq_len", "32",
        "--log_every", "2",
    ]
    ps = subprocess.Popen(
        [sys.executable, LAUNCHER, "--job_name", "ps", "--task_index", "0",
         *common],
        env=_env(), cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        worker = subprocess.run(
            [sys.executable, LAUNCHER, "--job_name", "worker",
             "--task_index", "0", *common],
            env=_env(), cwd=REPO, capture_output=True, text=True, timeout=300,
        )
        assert worker.returncode == 0, worker.stderr[-4000:]
        assert "TF1_PS_LAUNCHER_DONE" in worker.stdout, worker.stdout[-2000:]
        # the ps task is still parked in join() — the TF1 contract
        assert ps.poll() is None, "ps task exited instead of parking in join()"
    finally:
        ps.terminate()
        ps.wait(timeout=30)


def test_migrate_from_tf_example(tmp_path):
    """The migration showcase: real TF checkpoint -> pure-python bundle
    reader -> params tree -> training fed by a real tf.data pipeline."""
    pytest.importorskip("tensorflow")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "migrate_from_tf.py")],
        env=_env(), cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "MIGRATE_FROM_TF_DONE" in out.stdout, out.stdout[-2000:]
    line = [l for l in out.stdout.splitlines()
            if "MIGRATE_FROM_TF_DONE" in l][0]
    assert "step=10" in line
