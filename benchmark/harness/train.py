"""A training cell: the program's own step, state and loop, driven for a
window; then the plain reference over the same first steps."""

from __future__ import annotations

import gc
import math
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.harness import device as device_lib
from benchmark.harness import flops, program, traffic, weights
from benchmark.harness import train_reference as tr
from benchmark.harness.profile import traced
from benchmark.harness.spans import GcPauses, HostStalls, Spans
from benchmark.reference import precision as ref_precision


def build_step(cell, devices):
    """The program's workload and sharded step for a cell, from shapes
    alone: ``(workload, init, abstract_state, shardings, train_step,
    batch_shardings)``.  Nothing is allocated on the devices."""
    from distributed_tensorflow_tpu import cluster as cluster_lib
    from distributed_tensorflow_tpu import train_lib
    from distributed_tensorflow_tpu.models import get_workload
    from distributed_tensorflow_tpu.training import BF16, FP32

    trainer = cell.cell["trainer"]
    mesh = cluster_lib.build_mesh(
        cluster_lib.MeshConfig(**trainer["mesh"]), devices)
    workload = get_workload(
        cell.config["program"]["model"], mesh=mesh,
        config=program.program_config(cell.config),
        batch_size=int(cell.traffic["batch_size"]),
        seq_len=int(cell.traffic["seq_len"]),
        grad_accum_steps=int(trainer["grad_accum_steps"]),
        **trainer.get("workload_args", {}))
    init, abstract, shardings, train_step, batch_sh = train_lib.build_step(
        workload, mesh,
        precision={"bf16": BF16, "fp32": FP32}[trainer["precision"]],
        grad_accum_steps=int(trainer["grad_accum_steps"]),
        learning_rate=float(trainer["optimizer"]["peak_lr"]),
        total_steps=int(trainer["total_steps"]))
    return workload, init, abstract, shardings, train_step, batch_sh


def build(cell, seed: int, devices, spans: Spans, mark=lambda name: None):
    """The compiled step with its state, a loop over seeded batches, and
    the hooks that watch it: one object for set-up and window alike."""
    from distributed_tensorflow_tpu.data.pipeline import make_global_batches
    from distributed_tensorflow_tpu.training import TrainLoop
    from distributed_tensorflow_tpu.training.loop import Hook

    workload, init, abstract, shardings, train_step, batch_sh = build_step(
        cell, devices)
    mark("step_built")
    # The program's own init makes the state; its parameters are then
    # freed and replaced by the benchmark's seeded draw (Adam's moments and
    # the step count start at zero whatever the parameters are).
    state = init()
    for leaf in jax.tree.leaves(state.params):
        leaf.delete()
    state = state.replace(params=weights.make_params(
        seed, abstract.params, shardings.params))
    jax.block_until_ready(state)
    mark("state_made")

    class Watch(Hook):
        """Every loss the loop fetches, as it lands; closes the window.

        The host dispatches ahead of the device by one or two intervals of
        ``metrics_every`` steps, so it is not the host's clock that says
        when to stop: each fetch that lands tells how far the device is and
        how long its steps take, and from them how many steps fill the
        window.  The host's clock is the backstop."""

        def __init__(self):
            self.losses = {}
            self.grad_norms = {}
            self.last_step = 0
            self.landed = []        # (step, host time) of each fetch
            self.opened = self.deadline = self.stop_at = None

        def open(self, first_step, seconds):
            self.landed.clear()
            self.opened = (first_step, time.perf_counter())
            self.deadline = self.opened[1] + seconds
            return self.opened[1]

        def on_metrics(self, loop, metrics_step, metrics):
            now = time.perf_counter()
            self.losses[metrics_step] = metrics["loss"]
            self.grad_norms[metrics_step] = metrics.get("grad_norm")
            self.landed.append((metrics_step, now))
            if self.opened is not None and metrics_step > self.opened[0]:
                per_step = (now - self.opened[1]) / (
                    metrics_step - self.opened[0])
                self.stop_at = metrics_step + math.ceil(
                    max(self.deadline - now, 0.0) / per_step)

        def after_step(self, loop, step, metrics):
            self.last_step = step
            if self.deadline is None:
                return
            if time.perf_counter() >= self.deadline or (
                    self.stop_at is not None and step >= self.stop_at):
                loop.request_stop()

    def spanned_batches():
        it = make_global_batches(
            traffic.batches(cell.traffic, seed),
            batch_sh[workload.example_key])
        while True:
            with spans.span("next_batch"):
                batch = next(it)
            yield batch

    def spanned_step(state, batch, rng):
        with spans.span("dispatch"):
            return train_step(state, batch, rng)

    # TrainLoop reads this marker to pass one base key every step.
    spanned_step._dtt_in_step_rng = getattr(
        train_step, "_dtt_in_step_rng", False)
    watch = Watch()
    loop = TrainLoop(
        spanned_step, state, spanned_batches(), hooks=[watch],
        examples_per_step=int(cell.traffic["batch_size"]), metrics_every=1,
        rng=weights.seed_key(seed + 1))
    return loop, watch, abstract.params


def first_steps(cell, seed, loop, watch, abstract_params,
                 mark=lambda name: None):
    """Drive the loop through its first steps by its own call and feed,
    and read what the reference will be held against."""
    opt = cell.cell["trainer"]["optimizer"]
    steps = int(cell.cell["correct"]["steps"])
    norms = jax.jit(tr.leaf_norms)
    jax.block_until_ready(loop.run(1))
    mark("first_step")
    mu = {k: float(v) / (1.0 - float(opt["b1"]))
          for k, v in norms(_adam_mu(loop.state.opt_state)).items()}
    jax.block_until_ready(loop.run(steps - 1))
    delta = jax.jit(lambda p, key: tr.leaf_norms(jax.tree.map(
        jnp.subtract, p, weights.draw_params(key, abstract_params))))(
            loop.state.params, weights.seed_key(seed))
    mark("first_steps_read")
    return {"losses": [watch.losses[i] for i in range(1, steps + 1)],
            "grad_norms": mu, "grad_global_norm": watch.grad_norms[1],
            "delta_norms": {k: float(v) for k, v in delta.items()}}


def _adam_mu(opt_state):
    """The first-moment tree of an optax state, wherever the chain put it."""
    found = [s.mu for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise ValueError(f"expected one Adam first moment, found {len(found)}")
    return found[0]


def compare(program_side, reference_side, limits) -> Dict[str, Any]:
    """Each number compared, beside its limit."""
    loss_gap = max(abs(a - b) for a, b in zip(
        program_side["losses"], reference_side["losses"]))
    grad_gap, grad_leaf = tr.worst_leaf_gap(
        program_side["grad_norms"], reference_side["grad_norms"])
    delta_gap, delta_leaf = tr.worst_leaf_gap(
        program_side["delta_norms"], reference_side["delta_norms"])
    # The whole gradient's norm before clipping, as the step itself reports
    # it: errors of single elements average out over every leaf, so it is
    # steady from seed to seed and moves with the arithmetic's precision.
    whole = reference_side["grad_global_norm"]
    whole_gap = abs(program_side["grad_global_norm"] - whole) / whole
    rows = [
        {"number": "loss_gap_max", "value": loss_gap,
         "limit": limits["loss_gap_max"]},
        {"number": "first_grad_global_norm_gap", "value": whole_gap,
         "limit": limits["first_grad_global_norm_gap"]},
        {"number": "first_grad_norm_gap_worst_leaf", "value": grad_gap,
         "limit": limits["first_grad_norm_gap_worst_leaf"], "leaf": grad_leaf},
        {"number": "param_change_norm_gap_worst_leaf", "value": delta_gap,
         "limit": limits["param_change_norm_gap_worst_leaf"],
         "leaf": delta_leaf},
    ]
    for row in rows:
        row["ok"] = bool(math.isfinite(row["value"])
                         and row["value"] <= row["limit"])
    return {"rows": rows, "correct": all(r["ok"] for r in rows)}


def reference_side(cell, seed, abstract_params, devices, dot_name="exact"):
    correct = cell.cell["correct"]
    steps = int(correct["steps"])
    gen = traffic.batches(cell.traffic, seed)
    host = [next(gen) for _ in range(steps)]
    return tr.follow(
        program.reference_module(cell.config),
        ref_precision.BY_NAME[dot_name](), cell.config,
        cell.cell["trainer"]["optimizer"], seed, abstract_params, host,
        int(correct["reference_rows_per_block"]), devices)


def run(cell, *, seed: int, seconds: float, trace: bool, devices, peaks,
        started: float, say) -> Dict[str, Any]:
    spans = Spans()
    mark = lambda name: say("setup", at=name,
                            seconds=time.perf_counter() - started)
    mark("imports")
    loop, watch, abstract_params = build(cell, seed, devices, spans, mark)
    program_side = first_steps(cell, seed, loop, watch, abstract_params, mark)
    first = int(cell.cell["correct"]["steps"])
    spans.clear()
    window = min(seconds, float(cell.cell.get("trace_seconds", seconds))) \
        if trace else seconds

    # Set-up's garbage (the step's traces, the compiler's leftovers) is
    # collected and the survivors frozen here, so that no full collection
    # of that heap falls into the window; the collector stays on.
    gc.collect()
    gc.freeze()
    pauses, stalls = GcPauses(), HostStalls()
    # Set-up read every step's loss; the window fetches one loss in
    # ``metrics_every`` steps, an interval late, as the program's own runs
    # do, so the host runs that many steps ahead of the device and a host
    # that stalls for less than their time takes nothing from the device.
    every = loop.metrics_every = int(
        cell.cell["trainer"].get("metrics_every", 1))
    setup_s = time.perf_counter() - started

    with traced(trace, cell, spans) as profile, pauses, stalls:
        t0 = watch.open(first, window)
        state = loop.run(10 ** 9)
        jax.block_until_ready(state)
        t1 = time.perf_counter()
    gc.unfreeze()
    del state
    steps = watch.last_step - first
    window_s = t1 - t0
    # A loss that is not finite leaves parameters that are not, and every
    # later loss with them: the losses read stand for the steps between.
    seen = {s: v for s, v in watch.losses.items() if s > first}
    failed = sum(not math.isfinite(v) for v in seen.values())
    tokens = steps * traffic.tokens_per_batch(cell.traffic)
    rate = tokens / window_s / len(devices)
    memory = device_lib.memory_peak(devices)
    say("memory", **memory)
    # A fetch lands when the device has finished its step (the host waits
    # for it), so the time per step between two fetches is the device's.
    marks = [(first, t0)] + [m for m in watch.landed if m[0] > first]
    per_step = [(t - t_before) / (s - s_before) for (s_before, t_before),
                (s, t) in zip(marks, marks[1:])]
    say("window", steps=steps, window_s=window_s, tokens=tokens,
        metrics_every=every, losses_read=len(seen),
        last_loss=seen[max(seen)] if seen else None, setup_s=setup_s,
        step_s_between_fetches=per_step,
        host_stall_s_longest=stalls.longest,
        host_stall_ended_at_s=(stalls.longest_ended or t0) - t0,
        host_stall_s_total=stalls.total,
        gc_pause_s=pauses.seconds, gc_collections=pauses.count)

    # The reference runs after the program's state is freed, so the peak
    # above stays the program's.
    loop.state = None
    gc.collect()
    t_ref = time.perf_counter()
    verdict = compare(program_side,
                      reference_side(cell, seed, abstract_params, devices),
                      cell.cell["correct"]["limits"])
    for row in verdict["rows"]:
        say("compared", **row)
    say("reference", seconds=time.perf_counter() - t_ref)

    context = {
        "cell": cell, "peaks": peaks, "spans": spans, "window_s": window_s,
        "steps": steps, "chips": len(devices),
        "tokens_per_s_per_chip": rate,
        "memory_peak_bytes": memory["memory_peak_bytes"],
        "flops_per_token": flops.transformer_train_flops_per_token({
            **program.shape_of(cell.config),
            "seq_len": int(cell.traffic["seq_len"])}),
        "profile": profile.result,
    }
    return {
        "correct": verdict["correct"] and failed == 0,
        "attempted": steps, "failed": failed,
        "end_to_end": {"train_tokens_per_s_per_chip": rate,
                       "setup_s": setup_s},
        "context": context, "memory": memory,
    }
