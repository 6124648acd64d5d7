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


def fixed_reference(engine, prompt, max_new_tokens):
    """The fixed-batch answer for one prompt: a full padded-batch greedy
    generate, row 0.  Greedy decode is row-independent, so this is the
    token-for-token target for the continuous path."""
    import numpy as np

    rows = engine.bucket_rows(1)
    out = engine.generate(np.repeat(prompt[None, :], rows, axis=0),
                          max_new_tokens)
    return out[0]


def zero_cache(module, tokens, **call):
    """A decoder module's ``cache`` collection for this call, all zeros,
    from its shapes alone (``init`` is traced, never run)."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(lambda: module.init(
        jax.random.key(0), tokens, **call)["cache"])
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


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


def leave_in_order(grace_s: float = 1.0) -> None:
    """Leave a worker process of a multi-process test, for its script's
    last line.  Process 0 hosts JAX's coordination service, and a peer
    whose error poll sees that socket close aborts with exit code 1
    though its work is done — so process 0 must leave last.  Every other
    process says it is going (a key in the service's store) and goes;
    process 0 waits for all of them and a moment more.  ``os._exit``
    skips the atexit cluster-wide shutdown barrier, which only races on
    CPU test exits."""
    import time

    import jax
    from jax._src import distributed

    client = distributed.global_state.client
    me, n = jax.process_index(), jax.process_count()
    if client is not None and n > 1:
        if me:
            client.key_value_set(f"dtt_test_gone/{me}", "1")
        else:
            for peer in range(1, n):
                client.blocking_key_value_get(
                    f"dtt_test_gone/{peer}", 120_000)
            time.sleep(grace_s)
    os._exit(0)


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


def expert_forms_on_record(sched, *, experts: int, chunk: int) -> None:
    """A scheduler that has served a request over ``chunk``-token prefill
    launches says on its own record what its programs' expert layers took:
    ``stats()["moe_expert_form"]`` names the prefill and the decode program
    (``"<kind>/<tokens a layer's call sees>"``) with the form the call's
    static shape gives (``decoder_parts.expert_form``), a grouped program's
    static rows are the layout's, and the counts' leaf and keys are what
    they were."""
    from distributed_tensorflow_tpu.models import decoder_parts as parts
    from distributed_tensorflow_tpu.ops import grouped_matmul
    from distributed_tensorflow_tpu.serve.engine import moe_counts_of

    cfg = sched.engine.module.cfg
    k, held = cfg.num_experts_per_tok, cfg.held
    stats = sched.stats()
    forms, rows = stats["moe_expert_form"], stats["moe_grouped_rows"]
    assert forms == {p: form for p, (form, _) in
                     sched.engine.expert_forms().items()}
    for program, n in ((f"slot_prefill/{chunk}", chunk),
                       (f"slot_megastep/{sched.num_slots}", sched.num_slots)):
        form = parts.expert_form(n, k, experts)
        assert forms[program] == form, (program, forms)
        tm = grouped_matmul.tile_rows(n)
        assert rows[program] == (
            tm * grouped_matmul.static_tiles(n, k, held, tm)
            if form == grouped_matmul.GROUPED else 0)
    assert set(forms.values()) <= {grouped_matmul.GROUPED,
                                   grouped_matmul.DENSE}
    assert moe_counts_of(sched._cache).shape == (
        cfg.n_moe_layers if hasattr(cfg, "n_moe_layers")
        else cfg.num_hidden_layers, held + 3)
    assert stats["moe_experts_held"] == held
    for key in ("moe_assignments_here", "moe_assignments_absent",
                "moe_active_experts_per_step", "moe_layer_steps",
                "moe_load_max_over_mean"):
        assert isinstance(stats[key], float)
    assert stats["moe_layer_steps"] > 0


# What ``ContinuousScheduler._two_pool_stats_locked`` adds to ``stats()``, by
# what the family's cache geometry declares: a window ring, index keys under
# the table, a per-slot recurrent state.  A family with two of them reports
# both sets (PR 46 made the keys additive; the three families that have one
# report what they reported before).
POOL_STAT_KEYS = {
    "ring": {"kv_blocks_held_full", "kv_blocks_held_window", "kv_bytes_held",
             "kv_bytes_held_uniform", "window_ring_blocks",
             "window_blocks_recycled", "decode_live_positions_window"},
    "index": {"kv_blocks_held_latent", "kv_blocks_held_index",
              "kv_bytes_held", "kv_bytes_held_index",
              "decode_selected_positions"},
    "state": {"state_bytes_per_slot", "state_slots_live", "state_bytes_held",
              "state_resets", "kv_bytes_held"},
}


def pool_stat_keys_are(engine, *mechanisms: str) -> None:
    """A scheduler over ``engine`` that has served nothing reports exactly
    the pool keys of its family's ``mechanisms`` and holds nothing."""
    from distributed_tensorflow_tpu.serve import ContinuousScheduler

    with ContinuousScheduler(
            engine, num_slots=2, max_total_len=64, cache_mode="paged",
            block_size=16, prefill_budget=16, start=False) as sched:
        stats = sched.stats()
    every = set().union(*POOL_STAT_KEYS.values())
    want = set().union(*(POOL_STAT_KEYS[m] for m in mechanisms))
    assert set(stats) & every == want
    if want:
        assert stats["kv_bytes_held"] == 0
