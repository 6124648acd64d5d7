"""Shared test utilities (imported, not collected — no test_ prefix)."""

import json
import os
import socket
import subprocess
import sys
from typing import Dict, List, Optional, Sequence


def stream_fed_losses(wl, mesh, *, steps=2, total_steps=4, seed=1):
    """Tier-c feeding contract shared by the multiprocess worker scripts:
    train ``steps`` steps on IDENTICAL global batches on every host — each
    host generates the FULL stream (shard override 1/0) and contributes
    only the rows its devices own per ``host_batch_layout`` (a replicated
    batch dim: the whole batch; a data-sharded dim: this process's slice).
    Returns the per-step host losses."""
    import jax

    from distributed_tensorflow_tpu.data.pipeline import (
        host_batch_layout,
        set_stream_shard_override,
    )
    from distributed_tensorflow_tpu.train_lib import build_state_and_step
    from distributed_tensorflow_tpu.training import FP32

    state, _, step, batch_sh = build_state_and_step(
        wl, mesh, precision=FP32, total_steps=total_steps)
    bsh = batch_sh[wl.example_key]
    host_bs, _, idx = host_batch_layout(bsh, wl.batch_size)
    set_stream_shard_override(1, 0)
    try:
        stream = wl.data_fn(wl.batch_size)
        losses = []
        rng = jax.random.key(seed)
        for i in range(steps):
            full = next(stream)
            lo = idx * host_bs
            batch = {
                k: jax.make_array_from_process_local_data(
                    bsh, v[lo:lo + host_bs])
                for k, v in full.items()
            }
            state, m = step(state, batch, jax.random.fold_in(rng, i))
            losses.append(float(m["loss"]))
    finally:
        set_stream_shard_override(None)
    return losses


def free_ports(n: int) -> List[int]:
    """Allocate ``n`` distinct free localhost ports.

    All sockets stay open until every port is bound, so two calls cannot
    be handed the same just-released ephemeral port (the p0 == p1 race a
    close-then-rebind helper has).
    """
    socks = []
    try:
        for _ in range(n):
            s = socket.socket()
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind(("127.0.0.1", 0))
            socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def free_port() -> int:
    return free_ports(1)[0]


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spawn_worker_cluster(
    script: str,
    n: int = 2,
    *,
    args: Sequence[str] = (),
    extra_env: Optional[Dict[str, str]] = None,
) -> List[subprocess.Popen]:
    """Start ``n`` worker processes forming a localhost TF_CONFIG cluster.

    Each runs ``script`` via ``python -c`` with JAX pinned to CPU
    (``JAX_PLATFORMS=cpu``) — the shared bootstrap contract of every
    multiprocess test.
    """
    ports = free_ports(n)
    cluster = {"worker": [f"localhost:{p}" for p in ports]}
    procs = []
    for idx in range(n):
        env = dict(
            os.environ,
            TF_CONFIG=json.dumps(
                {"cluster": cluster, "task": {"type": "worker", "index": idx}}
            ),
            JAX_PLATFORMS="cpu",
        )
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script, *args],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        ))
    return procs


def join_workers(procs, *, timeout: int, fail) -> List[str]:
    """communicate() every worker; on any timeout kill ALL and call
    ``fail(msg)``.  Returns per-worker outputs."""
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            fail("worker cluster hung")
            return []
        outs.append(out)
    return outs
