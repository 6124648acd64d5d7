"""A serving cell: the program's engine and continuous scheduler under an
open-loop load at a fixed rate; then the plain reference over every request
that was served."""

from __future__ import annotations

import dataclasses
import gc
import math
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from benchmark.harness import device as device_lib
from benchmark.harness import program, traffic, weights
from benchmark.harness.profile import traced
from benchmark.harness.spans import GcPauses, HostStalls, Spans, XlaCompiles
from benchmark.harness.stats import percentile
from benchmark.reference import precision as ref_precision


@dataclasses.dataclass
class Served:
    """One request as the client saw it (times on ``time.monotonic``)."""
    request: traffic.Request
    due_t: float
    submit_t: Optional[float] = None
    future: Any = None
    error: Optional[str] = None
    withdrawn: bool = False     # cancelled by the client at the window's close
    batches: List = dataclasses.field(default_factory=list)  # (t, n tokens)
    tokens: Optional[np.ndarray] = None

    def on_token(self, toks):
        self.batches.append((time.monotonic(), len(toks)))

    @property
    def lateness_s(self):
        return None if self.submit_t is None else self.submit_t - self.due_t

    @property
    def ok(self):
        return (self.error is None and self.tokens is not None
                and len(self.tokens) == self.request.max_new_tokens
                and sum(n for _, n in self.batches) == len(self.tokens))

    def ttft_s(self, fallback_t):
        first = self.batches[0][0] if self.batches else fallback_t
        return first - self.due_t

    @property
    def tpot_s(self):
        n = sum(k for _, k in self.batches)
        if n < 2:
            return None
        return (self.batches[-1][0] - self.batches[0][0]) / (n - 1)


def build(cell, seed: int, devices):
    """Engine with the benchmark's seeded weights, and the scheduler."""
    from distributed_tensorflow_tpu import cluster as cluster_lib
    from distributed_tensorflow_tpu.serve import ContinuousScheduler
    from distributed_tensorflow_tpu.serve.engine import ServeEngine

    mesh = cluster_lib.build_mesh(
        cluster_lib.MeshConfig(**cell.cell.get("mesh", {})), devices)
    engine = ServeEngine(
        cell.config["program"]["model"], mesh=mesh,
        config=program.program_config(cell.config))
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), engine.params)
    shardings = jax.tree.map(lambda x: x.sharding, engine.params)
    engine.install_params(weights.make_params(seed, abstract, shardings))
    sched = ContinuousScheduler(engine, **cell.cell["scheduler"])
    return engine, sched, abstract


def warm_up(cell, sched, seed: int):
    """One request of every prompt length the mix can send, long enough
    to run the decode program: the cell's own shapes and no others."""
    rng = np.random.default_rng([seed, 6])
    vocab = int(cell.traffic["vocab_size"])
    steps = 2 * int(cell.cell["scheduler"].get("megastep", 1)) + 1
    futures = [sched.submit(rng.integers(0, vocab, n, dtype=np.int32),
                            max_new_tokens=steps)
               for n in traffic.prompt_lengths(cell.traffic)]
    for f in futures:
        f.result(timeout=1200.0)
    return len(futures)


def offer(requests, sched, spans: Spans, t0: float) -> List[Served]:
    """Open loop: every request goes in at its due instant, whether or not
    earlier ones have finished."""
    served = []
    for req in requests:
        rec = Served(req, t0 + req.due_s)
        with spans.span("wait_request"):
            delay = rec.due_t - time.monotonic()
            if delay > 0:
                time.sleep(delay)
        with spans.span("submit"):
            rec.submit_t = time.monotonic()
            try:
                rec.future = sched.submit(
                    req.prompt, max_new_tokens=req.max_new_tokens,
                    on_token=rec.on_token)
            except Exception as e:      # shed or refused: a failed request
                rec.error = f"{type(e).__name__}: {e}"
        served.append(rec)
    return served


def drain(served: List[Served], spans: Spans, deadline: float):
    with spans.span("drain"):
        for rec in served:
            if rec.future is None:
                continue
            try:
                rec.tokens = np.asarray(rec.future.result(
                    timeout=max(0.0, deadline - time.monotonic())))
            except Exception as e:      # undrained or failed mid-decode
                rec.error = f"{type(e).__name__}: {e}"


def withdraw_unstarted(served: List[Served], sched) -> int:
    """The client gives up, at the window's close, on every request that
    has had no token yet: queued, or admitted and not yet prefilled."""
    count = 0
    for rec in served:
        if (rec.future is not None and not rec.batches
                and not rec.future.done() and sched.cancel(rec.future.rid)):
            rec.withdrawn = True
            count += 1
    return count


def reference_gaps(cell, seed, abstract_params, prompts, picks,
                   dot_name="exact", pick_own=False):
    """For each request, at every answered position, how far the picked
    token's logit lies below the reference's best: one array a request.
    The reference runs once over prompt + picked tokens, several requests
    a forward.  Each request is padded to the shortest of the cell's
    ``reference_padded_lengths`` that holds it (the slot length alone
    where the cell gives none), and a forward of shorter rows takes as
    many more of them, so every forward holds the same number of
    positions and the reference compiles one program a length.  With
    ``pick_own`` it returns instead the tokens this arithmetic itself puts
    first at those positions (the control: a lower precision in the
    reference's place)."""
    import jax.numpy as jnp

    ref = program.reference_module(cell.config)
    dot = ref_precision.BY_NAME[dot_name]()
    f32 = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), abstract_params)
    params = weights.make_params(seed, f32)
    longest = int(cell.cell["scheduler"]["max_total_len"])
    positions = longest * int(
        cell.cell["correct"]["reference_rows_per_forward"])
    lengths = sorted(int(n) for n in cell.cell["correct"].get(
        "reference_padded_lengths", [longest]))
    if lengths[-1] != longest:
        raise ValueError(f"reference_padded_lengths {lengths} must end at "
                         f"the slot length {longest}")

    @jax.jit
    def forward(p, tokens, picked):
        logits = ref.logits(dot, cell.config, p, tokens)
        if pick_own:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        at = jnp.take_along_axis(logits, picked[..., None], axis=-1)[..., 0]
        return jnp.max(logits, axis=-1) - at

    by_length = {}
    for i, (prompt, pick) in enumerate(zip(prompts, picks)):
        needed = len(prompt) + len(pick) - 1
        by_length.setdefault(
            next(n for n in lengths if n >= needed), []).append(i)
    out = [None] * len(prompts)
    for length, members in sorted(by_length.items()):
        rows = positions // length
        for start in range(0, len(members), rows):
            tokens = np.zeros((rows, length), np.int32)
            picked = np.zeros((rows, length), np.int32)
            spans = []
            for row, i in enumerate(members[start:start + rows]):
                seq = np.concatenate([prompts[i], picks[i]])[:-1]
                tokens[row, :len(seq)] = seq
                first = len(prompts[i]) - 1
                picked[row, first:first + len(picks[i])] = picks[i]
                spans.append((i, first, first + len(picks[i])))
            result = np.asarray(forward(params, jnp.asarray(tokens),
                                        jnp.asarray(picked)))
            for row, (i, lo, hi) in enumerate(spans):
                out[i] = result[row, lo:hi]
    return out


def run(cell, *, seed: int, seconds: float, trace: bool, devices, peaks,
        started: float, say) -> Dict[str, Any]:
    spans = Spans()
    mark = lambda name: say("setup", at=name,
                            seconds=time.perf_counter() - started)
    mark("imports")
    engine, sched, abstract_params = build(cell, seed, devices)
    mark("engine_and_scheduler")
    warm_up(cell, sched, seed)
    mark("warmed_up")
    compiles_warm = engine.compile_stats()["compile_total"]
    window = min(seconds, float(cell.cell.get("trace_seconds", seconds))) \
        if trace else seconds
    # The traced run offers the timed run's traffic and closes earlier.
    requests = [r for r in traffic.open_loop_requests(
        cell.traffic, seed, seconds) if r.due_s < window]
    lead_in = float(cell.traffic["lead_in_s"])
    lead = [r for r in requests if r.due_s < 0]
    # Set-up's garbage (the engine's traces, the compiler's leftovers, the
    # requests just drawn) is collected and the survivors frozen here, as
    # the training driver does: the load generator shares the server's
    # interpreter, and a full collection of that heap stops both for a
    # quarter of a second (tools/silence_hunt.py).  The collector stays on.
    gc.collect()
    gc.freeze()
    xla = XlaCompiles().start()
    served = offer(lead, sched, spans, time.monotonic() + lead_in)
    mark("lead_in_offered")
    spans.clear()

    stalls, pauses = HostStalls(), GcPauses()
    with traced(trace, cell, spans) as profile, stalls, pauses:
        stats_start = sched.stats()
        setup_s = time.perf_counter() - started
        t0, t0_perf = time.monotonic(), time.perf_counter()
        in_window = offer([r for r in requests if r.due_s >= 0],
                          sched, spans, t0)
        with spans.span("wait_request"):
            time.sleep(max(0.0, t0 + window - time.monotonic()))
        t_end = t0 + window
        stats_end = sched.stats()
        served += in_window
        unfinished = sum(1 for r in served if r.future is not None
                         and not r.future.done())
        withdrawn = withdraw_unstarted(served, sched)
    answered = [r for r in served if not r.withdrawn]
    drain(answered, spans, t_end + float(cell.cell["drain_seconds"]))
    drained_t = time.monotonic()
    xla.stop()
    gc.unfreeze()
    compiles = engine.compile_stats()["compile_total"] - compiles_warm
    memory = device_lib.memory_peak(devices)
    say("memory", **memory)

    failed = sum(not r.ok for r in answered)
    timed = [r for r in in_window if not r.withdrawn]
    ttft = [r.ttft_s(drained_t) for r in timed]
    tpot = [r.tpot_s for r in timed if r.ok and r.tpot_s is not None]
    # Where in the window the tokens fell: a second in which the host was
    # taken from the process, or the device waited, shows as a dip.
    by_second = [0] * max(1, math.ceil(window))
    for r in served:
        for t, n in r.batches:
            if t0 <= t <= t_end:
                by_second[min(int(t - t0), len(by_second) - 1)] += n
    delivered = sum(by_second)
    say("window", offered=len(served), lead_in_requests=len(lead),
        answered=len(answered), failed=failed, unfinished_at_close=unfinished,
        withdrawn_at_close=withdrawn, window_s=window,
        queue_depth_at_open=stats_start.get("queue_depth"),
        queue_depth_at_close=stats_end.get("queue_depth"),
        drain_s=drained_t - t_end, tokens_in_window=delivered,
        tokens_by_second=by_second, host_stall_s_longest=stalls.longest,
        host_stall_ended_at_s=(stalls.longest_ended or 0.0) - t0_perf,
        host_stall_s_total=stalls.total, gc_pause_s=pauses.seconds,
        gc_pause_s_longest=pauses.longest, gc_collections=pauses.count,
        # (seconds after the window opened, seconds it took), lead-in too
        xla_compiles_after_warmup=[[t - t0_perf, s] for t, s in xla.ended],
        compile_post_warmup=int(compiles), setup_s=setup_s,
        ttft_samples=len(ttft), tpot_samples=len(tpot),
        ttft_p50_ms=1e3 * percentile(ttft, 50) if ttft else None,
        ttft_p95_ms=1e3 * percentile(ttft, 95) if ttft else None,
        tpot_p50_ms=1e3 * percentile(tpot, 50) if tpot else None,
        tpot_p95_ms=1e3 * percentile(tpot, 95) if tpot else None,
        lateness_p95_ms=1e3 * percentile(
            [r.lateness_s for r in served if r.lateness_s is not None], 95))
    for rec in answered:
        if not rec.ok:
            say("failed_request", due_s=rec.request.due_s, error=rec.error,
                tokens=None if rec.tokens is None else len(rec.tokens))
            break

    done = [r for r in answered if r.ok]
    sched.close()
    del sched, engine
    gc.collect()
    t_ref = time.perf_counter()
    gaps = reference_gaps(cell, seed, abstract_params,
                          [r.request.prompt for r in done],
                          [r.tokens for r in done])
    gap = max((float(g.max()) for g in gaps), default=float("inf"))
    limit = cell.cell["correct"]["limits"]["served_logit_gap_max"]
    say("compared", number="served_logit_gap_max", value=gap, limit=limit,
        ok=gap <= limit, requests=len(done),
        served_tokens=sum(len(r.tokens) for r in done),
        tokens_off_reference_best=int(sum(int((g > 0).sum()) for g in gaps)))
    say("compared", number="compile_post_warmup", value=int(compiles),
        limit=0, ok=compiles == 0)
    say("compared", number="requests_not_answered_in_full", value=failed,
        limit=0, ok=failed == 0)
    say("reference", seconds=time.perf_counter() - t_ref)

    context = {
        "cell": cell, "peaks": peaks, "spans": spans, "window_s": window,
        "chips": len(devices), "served": timed, "stats_start": stats_start,
        "stats_end": stats_end,
        "memory_peak_bytes": memory["memory_peak_bytes"],
        "profile": profile.result,
    }
    return {
        "correct": gap <= limit and compiles == 0 and failed == 0,
        "attempted": len(answered), "failed": failed,
        "end_to_end": {"serve_tokens_per_s": delivered / window,
                       "setup_s": setup_s},
        "context": context, "memory": memory,
    }
