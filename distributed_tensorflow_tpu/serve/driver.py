"""In-process serve loop: synthetic clients -> batcher -> engine.

The ``serve.py`` entrypoint and the benchmark's serving cells drive this.
No HTTP/stdin surface on purpose: the subsystem under test is checkpoint
restore + KV-cache decode + dynamic batching on the accelerator; a few
client threads submitting through ``DynamicBatcher`` exercise the same
coalescing/backpressure behavior a frontend would, without a transport
dependency in the repo.

Two scheduling disciplines, same client loop:

- fixed-batch (default): ``DynamicBatcher`` coalesces shape-uniform
  buckets, each flushed batch decodes the full shared horizon
  (``ServeEngine.generate_batch``);
- ``continuous=True``: ``DynamicBatcher(iteration_level=True)`` streams
  requests into a ``ContinuousScheduler`` that re-forms the decode batch
  every step over ONE resident KV cache — short requests retire
  immediately and new ones are admitted into their slots mid-flight.

Traffic is MIXED by default where it matters: ``prompt_lens`` cycles
prompt lengths and ``min_new_tokens`` (when set below ``max_new_tokens``)
cycles per-request horizons — the workload where iteration-level
scheduling beats request-level batching (short requests no longer pay for
the longest row in their batch).

Reported numbers: delivered tokens/sec (gpt2) or classified examples/sec,
per-request latency percentiles, and — under the continuous scheduler —
time-to-first-token percentiles, mean time-per-output-token and slot
occupancy, straight from the scheduler's counters.
"""

from __future__ import annotations

import dataclasses
import hashlib
import logging
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from distributed_tensorflow_tpu import cluster as cluster_lib
from distributed_tensorflow_tpu.obs import ServeMonitorHook, startup
from distributed_tensorflow_tpu.serve.batcher import (
    DynamicBatcher,
    ServeOverloadedError,
)
from distributed_tensorflow_tpu.serve import sampling as sampling_lib
from distributed_tensorflow_tpu.serve.continuous import ContinuousScheduler
from distributed_tensorflow_tpu.serve.engine import ServeEngine

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class ServeArgs:
    model: str = "gpt2"
    checkpoint_dir: Optional[str] = None
    steps: int = 32  # requests to drive through the loop
    max_batch_size: int = 8
    batch_timeout_ms: float = 5.0
    max_queue_size: int = 64
    max_new_tokens: int = 16
    # 0 = every request decodes max_new_tokens; >0 = per-request horizons
    # cycle between min and max (mixed traffic — the continuous scheduler's
    # home turf).
    min_new_tokens: int = 0
    prompt_len: int = 16
    # comma-separated prompt lengths to cycle ("8,16,24"); empty = uniform
    # prompt_len.
    prompt_lens: str = ""
    clients: int = 4
    preset: Optional[str] = None  # gpt2 config preset; None = auto by platform
    # continuous batching (serve/continuous.py)
    continuous: bool = False
    num_slots: int = 8
    # KV cache layout for the continuous scheduler: "dense" keeps the
    # (num_slots, max_total_len) resident cache; "paged" stores K/V in a
    # block pool indexed through per-slot block tables (serve/paged.py).
    cache_mode: str = "dense"
    block_size: int = 16
    # 0 = auto-size the pool to full capacity (num_slots * blocks-per-slot
    # + trash block — correctness default, no memory savings); smaller
    # pools trade admission backpressure for HBM.
    num_blocks: int = 0
    # "" = store the model's compute dtype; "int8" = per-token symmetric
    # quantization with f32 scales; any jnp dtype name ("bfloat16", ...)
    # stores that dtype directly.
    kv_dtype: str = ""
    # Partition the paged block pool over the mesh's data shards: each
    # shard owns num_blocks/data blocks and slot tables index only their
    # own shard's range (requires cache_mode="paged").
    per_shard_kv: bool = False
    # Content-addressed prefix caching (requires cache_mode="paged"):
    # requests whose prompt shares full leading blocks with an earlier
    # request map those blocks from cache (refcounted, copy-on-write)
    # and prefill only the uncached suffix.
    prefix_cache: bool = False
    # Chunked prefill: >0 bounds the prompt tokens prefilled per scheduler
    # iteration — a long prompt spreads over several iterations (chunks of
    # this size; ragged final chunk) while already-decoding slots keep
    # stepping every iteration, so decode TPOT never stalls behind a whale
    # prompt.  0 = classic one-shot prefill.  Greedy output is bit-identical
    # either way.
    prefill_budget: int = 0
    # Megastep decode: K > 1 fuses K decode iterations into ONE compiled
    # program (lax.scan on device) — one host dispatch + one
    # (num_slots, K) fetch per K tokens.  Rows hitting their eos/horizon
    # mid-megastep stop advancing on device and are trimmed on host, so
    # greedy output is bit-identical K on vs off.  1 = classic
    # one-launch-per-token path.  "auto" probes the dispatch-vs-step
    # time ratio on a throwaway scheduler BEFORE the timed run and pins
    # the chosen K for the run itself, so compiled-program identity
    # stays stable (no post-warmup recompiles).
    megastep: Any = 1
    # Deep async decode: dispatch each launch before resolving the
    # previous ones, so admission/prefill/retirement run while the
    # device computes.  Costs up to async_depth - 1 iterations of
    # delivery lag; greedy output stays bit-identical on vs off.
    async_decode: bool = False
    # Launches the async ring may hold in flight (1 = dispatch-then-
    # resolve, 2 = the classic double buffer).
    async_depth: int = 2
    # Speculative decoding: k >= 1 turns each decode iteration into
    # draft-and-verify — an n-gram prompt-lookup drafter (no second
    # model) proposes up to k tokens per slot from the slot's own
    # prompt+output history, and ONE (num_slots, k+1) verify forward
    # accepts the longest agreeing prefix + a bonus token per slot.
    # Greedy output is bit-identical k on vs off; sampled stays
    # distribution-exact.  0 = off.
    spec_k: int = 0
    # Longest history n-gram the drafter matches (it backs off to 1).
    spec_ngram: int = 3
    # SLO-aware scheduling (continuous only): admission ranks requests
    # by (priority tier, deadline slack, arrival) instead of FIFO, and —
    # paged mode — block pressure preempts the lowest tier, swapping its
    # KV blocks to host RAM (or dropping them for recompute, whichever
    # the cost model picks) and resuming when pressure clears.
    slo_scheduling: bool = False
    # Contexts shorter than this always take the recompute path on
    # preemption (re-prefill beats moving a few KV bytes twice).
    swap_min_tokens: int = 32
    # Starvation aging: a queued request gains one effective priority
    # tier per this many seconds waited, so tier 0 cannot starve forever
    # behind a steady tier-9 stream.
    starvation_age_s: float = 5.0
    # Repetitive traffic mix: >0 builds each prompt's tail by tiling a
    # motif of this many tokens instead of i.i.d. random tokens — the
    # structured/repetitive workload prompt-lookup drafting wins on
    # (tiny greedy models loop on such prompts, so drafts keep landing).
    # 0 keeps the fully-random mix.
    prompt_period: int = 0
    # Shared-prefix traffic mix: >0 prepends a system prompt of this many
    # tokens to every request, drawn from `shared_prefix_groups` distinct
    # prefixes — the workload prefix caching exists for.  0 keeps the
    # fully-random mix.
    shared_prefix_len: int = 0
    shared_prefix_groups: int = 2
    # fleet (serve/fleet/): >1 runs N replica engines behind a
    # load-aware FleetRouter (requires --continuous on gpt2).
    num_replicas: int = 1
    # >0 polls checkpoint_dir every that-many seconds and hot-reloads new
    # steps into every replica without dropping in-flight requests.
    reload_poll_s: float = 0.0
    # graceful-drain budget on SIGTERM/KeyboardInterrupt: stop admitting,
    # finish in-flight decodes, shed the still-queued.
    drain_timeout_s: float = 10.0
    # sampling (greedy argmax when temperature == 0)
    temperature: float = 0.0
    top_k: int = 0
    # "" = every request uses the scalars above.  A mix spec (e.g.
    # "greedy:0.5,t0.8k40:0.3,t1.0p0.9:0.2") gives each request its own
    # SamplingParams by deterministic weighted round-robin — requires
    # --continuous, where the whole mix shares ONE compiled program set
    # (per-slot runtime vectors, never a compile-cache key).
    sampling_mix: str = ""
    # mesh axes (data=-1 absorbs the rest, as in train.py)
    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    log_every: int = 16
    seed: int = 0
    # observability: 0 = no scrape endpoint; >0 binds a Prometheus
    # /metrics HTTP server on that port for the run's lifetime.
    metrics_port: int = 0
    # streaming gateway (serve/gateway/): 0 = no HTTP front door; >0
    # binds GatewayServer on that port for the run's lifetime — POST
    # /v1/generate (SSE per-token streaming with stream=true), POST
    # /v1/cancel/<gid>, max-inflight admission control.  Requires the
    # continuous gpt2 path for streaming; non-streaming works anywhere.
    gateway_port: int = 0
    # Gateway admission limit: requests in flight beyond this answer
    # 429 with a Retry-After header instead of queueing unboundedly.
    max_inflight: int = 64
    # >0 tiers the gateway's inflight gate: priority p's limit is
    # max_inflight - (9 - p) * priority_headroom (floored at 1), so
    # under load the lowest tiers shed (429) first.
    priority_headroom: int = 0
    # "" = tracing off; a path enables the flight recorder and writes the
    # Chrome trace-event JSON (Perfetto-loadable) there at shutdown.
    trace_out: str = ""
    # "" = the synthetic closed-loop client mix above; a trace spec
    # ("poisson:n=64,whale_frac=0.2" / "diurnal:..." / "burst:...")
    # replaces it with the OPEN-LOOP load generator (serve/loadgen.py):
    # arrivals fire on schedule whether or not earlier requests
    # finished, 429s count as real shed, and the JSON line reports
    # goodput-under-SLO.  Requires the continuous gpt2 path.
    loadgen_trace: str = ""
    # Mean arrival rate (req/s) for --loadgen_trace specs that don't
    # pin their own rate=.
    arrival_rate: float = 8.0
    # "" = lifecycle attribution off; a path attaches the per-request
    # LifecycleRecorder (obs/lifecycle.py) and streams its typed events
    # there as JSONL.  The JSON line gains the per-phase breakdown keys.
    lifecycle_log: str = ""


# Models served through the KV-cache decode path (generate and the slot
# programs), each with its presets where no --preset is given: (the CPU
# smoke's config, the chip's).  For glm4_moe_lite the chip's is one chip's
# share of an 8-chip deployment (the whole model is 60 GB in bfloat16), for
# mellum one chip's share of a 4-chip host (24 GB whole), for glm_moe_dsa
# one chip's share of a v5e-256 (32 chips a layer, 5 of a stage's layers),
# for solar_open2 one chip's share of a v5e-128 (16 chips a layer, one
# period of 4 layers), for dots3_note one chip's share of a v5e-256 (32
# chips a layer, the dense layer and one period).
# Every other model is batched classification.
_AUTO_PRESETS = {"gpt2": ("tiny", "medium"),
                 "glm4_moe_lite": ("tiny", "v5e8_share"),
                 "mellum": ("tiny", "v5e4_share"),
                 "glm_moe_dsa": ("tiny", "v5e256_share"),
                 "solar_open2": ("tiny", "v5e128_share"),
                 "dots3_note": ("tiny", "v5e256_share")}
DECODER_MODELS = tuple(_AUTO_PRESETS)


def _auto_preset(args: ServeArgs) -> Optional[str]:
    if args.preset:
        return args.preset
    if args.model not in _AUTO_PRESETS:
        return None
    # CPU smoke serves the test config; real TPUs serve the paper's model.
    # The choice is logged and the JSON line carries ``preset`` and
    # ``device``, so a tiny CPU run can never be read as the chip.
    platform = cluster_lib.device_summary()["platform"]
    on_cpu, on_chip = _AUTO_PRESETS[args.model]
    preset = on_chip if platform == "tpu" else on_cpu
    logger.info("no --preset given: serving %s %r on platform %r",
                args.model, preset, platform)
    return preset


def _identity_keys(engine: ServeEngine) -> Dict[str, Any]:
    """What ran where — on every JSON line this driver returns."""
    return {
        "preset": engine.preset,
        "device": cluster_lib.device_summary(),
    }


def _horizons(args: ServeArgs) -> List[int]:
    """Per-request max_new_tokens cycle for mixed traffic."""
    hi = args.max_new_tokens
    lo = args.min_new_tokens
    if lo <= 0 or lo >= hi:
        return [hi]
    return [hi, lo, max(lo, (lo + hi) // 2), hi]


def _cache_kwargs(args: ServeArgs) -> Dict[str, Any]:
    """ContinuousScheduler cache-layout kwargs from the flag surface."""
    if args.cache_mode == "dense":
        return {"cache_mode": "dense"}
    return {
        "cache_mode": args.cache_mode,
        "block_size": args.block_size,
        "num_blocks": args.num_blocks or None,
        "kv_dtype": args.kv_dtype or None,
        "per_shard_kv": args.per_shard_kv,
        "prefix_cache": args.prefix_cache,
    }


def _slo_kwargs(args: ServeArgs) -> Dict[str, Any]:
    """ContinuousScheduler SLO kwargs from the flag surface."""
    if not args.slo_scheduling:
        return {}
    return {
        "slo_scheduling": True,
        "swap_min_tokens": args.swap_min_tokens,
        "starvation_age_s": args.starvation_age_s,
    }


def _prompt_lengths(args: ServeArgs) -> List[int]:
    if not args.prompt_lens:
        return [args.prompt_len]
    lens = [int(x) for x in args.prompt_lens.split(",") if x.strip()]
    return lens or [args.prompt_len]


def _payload_parts(payload) -> Tuple[np.ndarray, int]:
    """(prompt, max_new_tokens) of one gpt2 payload — the plain tuple
    form or the dict form a ``--sampling_mix`` run submits."""
    if isinstance(payload, dict):
        return payload["prompt"], payload["max_new_tokens"]
    return payload


def _make_requests(args: ServeArgs, engine: ServeEngine,
                   rng: np.random.Generator):
    """One synthetic payload per request.  gpt2 payloads are (prompt,
    max_new_tokens) tuples — both paths serve the SAME mixed traffic.
    ``sampling_mix`` upgrades them to dicts carrying each request's own
    ``SamplingParams`` (same prompts, same horizons)."""
    if args.model in DECODER_MODELS:
        vocab = engine.module.cfg.vocab_size
        lens = _prompt_lengths(args)
        horizons = _horizons(args)
        # Shared-prefix mix: request i carries system prompt i % K plus
        # its own random tail of the cycled length — the distinct-prefix
        # groups are what the prefix cache's hit rate is measured over.
        assigner = None
        if args.sampling_mix:
            assigner = sampling_lib.MixAssigner(
                sampling_lib.parse_sampling_mix(args.sampling_mix))
        prefixes = None
        if args.shared_prefix_len > 0:
            prefixes = [
                rng.integers(0, vocab, size=(args.shared_prefix_len,),
                             dtype=np.int32)
                for _ in range(max(1, args.shared_prefix_groups))]
        payloads = []
        for i in range(args.steps):
            n = lens[i % len(lens)]
            if args.prompt_period > 0:
                # Repetitive mix: tile a per-request motif to the cycled
                # length — the structured workload the prompt-lookup
                # drafter exists for.
                motif = rng.integers(
                    0, vocab, size=(min(args.prompt_period, n),),
                    dtype=np.int32)
                tail = np.tile(motif, -(-n // motif.size))[:n]
            else:
                tail = rng.integers(0, vocab, size=(n,), dtype=np.int32)
            prompt = (tail if prefixes is None
                      else np.concatenate([prefixes[i % len(prefixes)],
                                           tail]))
            if assigner is None:
                payloads.append((prompt, horizons[i % len(horizons)]))
            else:
                payloads.append({
                    "prompt": prompt,
                    "max_new_tokens": horizons[i % len(horizons)],
                    "sampling": assigner.next(),
                })
        return payloads
    batch = next(engine.workload.data_fn(max(2, args.max_batch_size)))
    n = len(next(iter(batch.values())))
    return [{k: np.asarray(v[i % n]) for k, v in batch.items()
             if k != "label"} for i in range(args.steps)]


def run_serve(args: ServeArgs,
              engine: Optional[ServeEngine] = None) -> Dict[str, Any]:
    """Drive ``args.steps`` requests; returns the serve metrics dict.

    Pass ``engine`` to reuse one restored/compiled engine across runs."""
    own_engine = engine is None
    if own_engine:
        mesh = cluster_lib.build_mesh(cluster_lib.MeshConfig(
            data=args.data, fsdp=args.fsdp, tensor=args.tensor))
        overrides: Dict[str, Any] = {}
        preset = _auto_preset(args)
        if preset:
            overrides["preset"] = preset
        engine = ServeEngine(
            args.model, mesh=mesh, checkpoint_dir=args.checkpoint_dir,
            seed=args.seed, **overrides)
    server = None
    if args.metrics_port:
        from distributed_tensorflow_tpu.obs.exporters import MetricsServer

        server = MetricsServer(port=args.metrics_port)
    if args.trace_out:
        from distributed_tensorflow_tpu.obs.trace import default_tracer

        default_tracer().enable()
    try:
        return _drive(args, engine)
    finally:
        if args.trace_out:
            from distributed_tensorflow_tpu.obs.exporters import (
                write_chrome_trace,
            )

            write_chrome_trace(args.trace_out)
        if server is not None:
            server.close()
        if own_engine:
            engine.close()


def _make_batcher(args: ServeArgs, engine: ServeEngine,
                  lifecycle=None) -> DynamicBatcher:
    """The scheduling discipline behind one run: fixed buckets or
    iteration-level streaming into a continuous scheduler."""
    if args.model not in DECODER_MODELS:
        return DynamicBatcher(
            engine.classify_batch,
            max_batch_size=args.max_batch_size,
            batch_timeout_ms=args.batch_timeout_ms,
            max_queue_size=args.max_queue_size,
        )
    if args.continuous:
        cfg = engine.module.cfg
        need = max(p.shape[0] + m for p, m in
                   map(_payload_parts,
                       _make_requests(args, engine,
                                      np.random.default_rng(0))))
        scheduler = ContinuousScheduler(
            engine,
            num_slots=args.num_slots,
            max_total_len=min(cfg.n_positions, need),
            max_queue_size=args.max_queue_size,
            temperature=args.temperature,
            top_k=args.top_k,
            prefill_budget=args.prefill_budget,
            megastep=args.megastep,
            async_decode=args.async_decode,
            async_depth=args.async_depth,
            spec_k=args.spec_k or None,
            spec_ngram=args.spec_ngram,
            lifecycle=lifecycle,
            **_slo_kwargs(args),
            **_cache_kwargs(args),
        )
        return DynamicBatcher(iteration_level=True, scheduler=scheduler)

    def run_batch(payloads: List[Tuple[np.ndarray, int]]) -> List[Any]:
        # Request-level batching decodes the SHARED horizon for the whole
        # batch and slices each row to its own request — exactly the
        # short-pays-for-long cost continuous batching removes.
        gen = engine.generate_batch(
            [p for p, _ in payloads], args.max_new_tokens,
            temperature=args.temperature, top_k=args.top_k)
        return [g[:m] for (_, m), g in zip(payloads, gen)]

    return DynamicBatcher(
        run_batch,
        max_batch_size=args.max_batch_size,
        batch_timeout_ms=args.batch_timeout_ms,
        max_queue_size=args.max_queue_size,
        bucket_fn=lambda payload: len(payload[0]),
    )


def _make_fleet(args: ServeArgs, engine: ServeEngine):
    """N replicas behind a ``FleetRouter``: replica 0 reuses the caller's
    engine, the rest construct their own on the SAME mesh (same preset /
    checkpoint / seed, so fresh-init replicas serve identical weights).
    ``reload_poll_s > 0`` + a checkpoint dir attaches the hot-reload
    watcher, owned (and closed) by the router."""
    from distributed_tensorflow_tpu.serve.fleet import (
        CheckpointWatcher,
        FleetRouter,
        Replica,
    )

    cfg = engine.module.cfg
    need = max(p.shape[0] + m for p, m in
               map(_payload_parts,
                   _make_requests(args, engine, np.random.default_rng(0))))
    overrides: Dict[str, Any] = {}
    preset = _auto_preset(args)
    if preset:
        overrides["preset"] = preset
    replicas = []
    for i in range(args.num_replicas):
        eng = engine if i == 0 else ServeEngine(
            args.model, mesh=engine.mesh,
            checkpoint_dir=args.checkpoint_dir, seed=args.seed,
            **overrides)
        scheduler = ContinuousScheduler(
            eng,
            num_slots=args.num_slots,
            max_total_len=min(cfg.n_positions, need),
            max_queue_size=args.max_queue_size,
            temperature=args.temperature,
            top_k=args.top_k,
            prefill_budget=args.prefill_budget,
            megastep=args.megastep,
            async_decode=args.async_decode,
            async_depth=args.async_depth,
            spec_k=args.spec_k or None,
            spec_ngram=args.spec_ngram,
            **_slo_kwargs(args),
            name=f"serve-fleet-r{i}",
            **_cache_kwargs(args),
        )
        replicas.append(Replica(i, eng, scheduler, owns_engine=(i > 0)))
    watcher = None
    if args.reload_poll_s > 0 and args.checkpoint_dir:
        from distributed_tensorflow_tpu.checkpoint import CheckpointManager

        watcher = CheckpointWatcher(
            CheckpointManager(args.checkpoint_dir), replicas,
            poll_interval_s=args.reload_poll_s, owns_manager=True)
    return FleetRouter(replicas, watcher=watcher)


def _resolve_megastep(args: ServeArgs, engine: ServeEngine,
                      payloads) -> int:
    """Resolve ``--megastep=auto`` to a concrete K before the timed run.

    A throwaway scheduler runs with ``megastep="auto"`` on the SAME
    engine and replays the run's own traffic until the autotuner has
    enough dispatch/step timing samples to freeze its pick.  The timed
    run (and its ``_warm`` pass) then gets the frozen K as a plain int,
    so every program the run launches compiles during warmup and
    compiled-program identity stays stable — ``compile_post_warmup``
    must not move because K was chosen dynamically."""
    if args.megastep != "auto":
        return int(args.megastep)
    if args.model not in DECODER_MODELS or not args.continuous:
        raise ValueError(
            "--megastep=auto autotunes the continuous gpt2 decode loop "
            "(--continuous); fixed-batch decode has no megastep")
    cfg = engine.module.cfg
    need = max(p.shape[0] + m for p, m in map(_payload_parts, payloads))
    warm_kwargs = {**_cache_kwargs(args), "prefix_cache": False} \
        if args.cache_mode == "paged" else _cache_kwargs(args)
    probe = ContinuousScheduler(
        engine,
        num_slots=args.num_slots,
        max_total_len=min(cfg.n_positions, need),
        temperature=args.temperature,
        top_k=args.top_k,
        prefill_budget=args.prefill_budget,
        megastep="auto",
        async_decode=args.async_decode,
        async_depth=args.async_depth,
        spec_k=args.spec_k or None,
        spec_ngram=args.spec_ngram,
        **_slo_kwargs(args),
        **warm_kwargs,
    )
    try:
        deadline = time.monotonic() + 120.0
        i = 0
        while (not probe.stats()["megastep_autotune_frozen"]
               and time.monotonic() < deadline):
            batch = []
            for _ in range(max(2, args.num_slots)):
                p, m = _payload_parts(payloads[i % len(payloads)])
                batch.append(probe.submit(p, max_new_tokens=m))
                i += 1
            for f in batch:
                f.result(timeout=600.0)
        k = int(probe.stats()["megastep"])
    finally:
        probe.close()
    logger.info("megastep=auto resolved to K=%d before the timed run", k)
    return k


def _warm(args: ServeArgs, engine: ServeEngine, payloads) -> None:
    """Compile outside the timed window: the fixed path warms the padded
    full-batch prefill+decode programs; the continuous path warms the
    slot prefill (per prompt length) and the (num_slots, 1) step."""
    if args.model not in DECODER_MODELS:
        engine.classify_batch(payloads[: min(len(payloads),
                                             args.max_batch_size)])
        return
    if args.continuous:
        # The warm scheduler runs with the prefix cache OFF: the jitted
        # prefill program depends only on the token-suffix LENGTH (the
        # start offset is a dynamic argument), so a full-length prefill
        # of T tokens compiles exactly the program a cached request with
        # a T-token uncached suffix will launch.
        warm_kwargs = {**_cache_kwargs(args), "prefix_cache": False} \
            if args.cache_mode == "paged" else _cache_kwargs(args)
        # Warming with the SAME prefill_budget compiles the chunk shapes
        # the timed run will launch: chunk lengths depend only on the
        # remaining prompt length (the start offset is dynamic), so a
        # donor prompt of each expected suffix length walks exactly the
        # budget-size chunks plus its ragged final chunk.
        # Same megastep too: the K-step scan is its own compiled program
        # (keyed on K), so the timed run must not pay its compile.
        # Same async_decode: the double-buffered loop routes EVERY K
        # (including K=1) through the megastep program, so the warm
        # traffic must walk the same dispatch path the timed run will.
        warm_sched = ContinuousScheduler(
            engine, num_slots=args.num_slots,
            max_total_len=min(engine.module.cfg.n_positions,
                              max(p.shape[0] + m for p, m in
                                  map(_payload_parts, payloads))),
            temperature=args.temperature, top_k=args.top_k,
            prefill_budget=args.prefill_budget,
            megastep=args.megastep,
            async_decode=args.async_decode,
            async_depth=args.async_depth,
            spec_k=args.spec_k or None,
            spec_ngram=args.spec_ngram,
            **_slo_kwargs(args),
            **warm_kwargs)
        lengths = sorted({_payload_parts(p)[0].shape[0] for p in payloads})
        warm_lengths = set(lengths)
        if args.prefix_cache and args.shared_prefix_len > 0:
            # Suffix shapes the timed run will launch once each group's
            # prefix is cached: total length minus the block-aligned
            # cached-prefix depth.
            aligned = (args.shared_prefix_len // args.block_size) \
                * args.block_size
            for length in lengths:
                s = min(aligned,
                        (length - 1) // args.block_size * args.block_size)
                if 0 < s < length:
                    warm_lengths.add(length - s)
        futs = []
        for length in sorted(warm_lengths):
            donor = next(p for p, _ in map(_payload_parts, payloads)
                         if p.shape[0] >= length)
            futs.append(warm_sched.submit(donor[:length],
                                          max_new_tokens=2))
        for f in futs:
            f.result(timeout=600.0)
        warm_sched.close()
        return
    warm = payloads[: min(len(payloads), args.max_batch_size)]
    gen = engine.generate_batch(
        [p for p, _ in warm], args.max_new_tokens,
        temperature=args.temperature, top_k=args.top_k)
    del gen


_BREAKDOWN_PHASES = ("queue_wait", "prefill", "decode_compute",
                     "fetch_wait", "swap", "scheduler_stall")


def _lifecycle_keys(stats: Dict[str, float], args: ServeArgs
                    ) -> Dict[str, Any]:
    """Per-phase attribution keys for the JSON line (the scheduler's
    ``stats()`` already merged the recorder's aggregates)."""
    out: Dict[str, Any] = {
        "lifecycle_requests_total": int(
            stats.get("lifecycle_requests_total", 0.0)),
        "lifecycle_events_total": int(
            stats.get("lifecycle_events_total", 0.0)),
        "lifecycle_dropped_total": int(
            stats.get("lifecycle_dropped_total", 0.0)),
        "breakdown_sum_to_wall_ratio": round(
            stats.get("breakdown_sum_to_wall_ratio", 0.0), 4),
    }
    for ph in _BREAKDOWN_PHASES:
        out[f"breakdown_{ph}_p99_ms"] = round(
            stats.get(f"breakdown_{ph}_p99_ms", 0.0), 3)
    for ph in ("queue_wait", "prefill", "swap"):
        out[f"ttft_breakdown_{ph}_p99_ms"] = round(
            stats.get(f"ttft_breakdown_{ph}_p99_ms", 0.0), 3)
    if args.lifecycle_log:
        out["lifecycle_log"] = args.lifecycle_log
    return out


def _drive_loadgen(args: ServeArgs, engine: ServeEngine, batcher,
                   monitor, *, gateway=None, lifecycle=None
                   ) -> Dict[str, Any]:
    """Open-loop trace replay: the loadgen arrival process replaces the
    closed-loop synthetic clients, so overload shows up as shed + missed
    SLOs in the JSON line instead of a quietly degraded arrival rate."""
    from distributed_tensorflow_tpu.serve import loadgen as loadgen_lib

    cfg = engine.module.cfg
    # Same capacity the batcher was sized for: prompts clamp to it.
    need = max(p.shape[0] + m for p, m in
               map(_payload_parts,
                   _make_requests(args, engine, np.random.default_rng(0))))
    kwargs = loadgen_lib.parse_trace_spec(
        args.loadgen_trace, rate=args.arrival_rate, seed=args.seed)
    n = int(kwargs.pop("n"))
    kwargs.setdefault("vocab", int(cfg.vocab_size))
    kwargs.setdefault("max_total_len", min(cfg.n_positions, need))
    trace = loadgen_lib.build_trace(n, **kwargs)
    compile_warm = engine.compile_stats()["compile_total"]
    report = loadgen_lib.run_trace(
        batcher.scheduler, trace, lifecycle=lifecycle)
    stats = batcher.stats()
    gstats = None
    if gateway is not None:
        gstats = gateway.stats()
        gateway.close(timeout=args.drain_timeout_s)
    batcher.close()
    monitor.log(n)
    cstats = engine.compile_stats()
    out: Dict[str, Any] = {
        "model": args.model,
        "scheduler": "continuous",
        "loadgen_trace": args.loadgen_trace,
        "arrival_rate": float(kwargs.get("rate", args.arrival_rate)),
        "requests": int(report["requests_total"]),
        "completed": int(report["completed"]),
        "shed": int(report["shed"]),
        "errors": int(report["errors"]),
        "shed_rate": round(report["shed_rate"], 4),
        "goodput_under_slo": round(report["goodput_under_slo"], 4),
        "goodput_requests": int(report["goodput_requests"]),
        "tokens_generated": int(report["tokens_emitted"]),
        "tokens_per_sec": round(report["tokens_per_sec"], 2),
        "elapsed_s": round(report["wall_s"], 4),
        "client_ttft_p50_ms": round(report["client_ttft_p50_ms"], 3),
        "client_ttft_p99_ms": round(report["client_ttft_p99_ms"], 3),
        "tokens_checksum": report["tokens_checksum"],
        "by_tier": report["by_tier"],
        "by_scenario": report["by_scenario"],
        "slo_scheduling": bool(args.slo_scheduling),
        "checkpoint_step": engine.restored_step,
        "compile_total": int(cstats["compile_total"]),
        "compile_post_warmup": int(cstats["compile_total"] - compile_warm),
        **_identity_keys(engine),
    }
    out.update(_lifecycle_keys(stats, args))
    if gstats is not None:
        out["gateway_port"] = int(args.gateway_port)
        out["gateway_accepted"] = int(gstats["gateway_accepted"])
        out["gateway_throttled"] = int(gstats["gateway_throttled"])
    return out


def _drive(args: ServeArgs, engine: ServeEngine) -> Dict[str, Any]:
    no_dense = engine.workload.serve_refusals.get("dense_cache")
    if no_dense and not (args.continuous and args.cache_mode == "paged"):
        raise ValueError(
            f"model {args.model!r} is served with --continuous "
            f"--cache_mode=paged only: {no_dense}")
    if args.sampling_mix and not (args.model in DECODER_MODELS
                                  and args.continuous):
        raise ValueError(
            "--sampling_mix requires the continuous gpt2 path "
            "(--continuous); per-request sampling rides the slot "
            "programs' runtime vectors")
    if args.slo_scheduling and not (args.model in DECODER_MODELS
                                    and args.continuous):
        raise ValueError(
            "--slo_scheduling requires the continuous gpt2 path "
            "(--continuous); fixed-batch scheduling has no admission "
            "ranking or preemption")
    lifecycle = None
    if args.loadgen_trace or args.lifecycle_log:
        if not (args.model in DECODER_MODELS and args.continuous
                and args.num_replicas == 1):
            raise ValueError(
                "--loadgen_trace / --lifecycle_log require the "
                "single-replica continuous gpt2 path (--continuous): "
                "the open-loop harness and the lifecycle hooks drive "
                "the iteration-level scheduler directly")
        from distributed_tensorflow_tpu.obs.lifecycle import (
            LifecycleRecorder,
        )

        lifecycle = LifecycleRecorder(jsonl_path=args.lifecycle_log or None)
    rng = np.random.default_rng(args.seed)
    payloads = _make_requests(args, engine, rng)
    megastep_auto = args.megastep == "auto"
    if megastep_auto:
        # Resolve BEFORE warm/batcher construction: the warm pass then
        # compiles the chosen K's programs, and the timed run never
        # sees a dynamic K.
        args = dataclasses.replace(
            args, megastep=_resolve_megastep(args, engine, payloads))
    is_lm = args.model in DECODER_MODELS
    fleet = is_lm and args.continuous and args.num_replicas > 1
    if args.num_replicas > 1 and not fleet:
        raise ValueError(
            "--num_replicas > 1 requires the continuous gpt2 path "
            "(--continuous); fixed-batch fleets are not a thing here")
    if fleet:
        batcher = _make_fleet(args, engine)
        for rep in batcher.replicas:
            _warm(args, rep.engine, payloads)
    else:
        _warm(args, engine, payloads)
        batcher = _make_batcher(args, engine, lifecycle=lifecycle)
    gateway = None
    if args.gateway_port:
        from distributed_tensorflow_tpu.serve.gateway import GatewayServer

        # The front door rides the SAME backend the synthetic clients
        # drive in-process — routing, hot reload, and drain compose.
        gateway = GatewayServer(batcher, port=args.gateway_port,
                                max_inflight=args.max_inflight,
                                priority_headroom=args.priority_headroom)
        logger.info(
            "gateway listening on %s:%d (max_inflight=%d, "
            "priority_headroom=%d)",
            gateway.host, gateway.port, args.max_inflight,
            args.priority_headroom)
    # Ready: engine made, programs warmed, front door open.  One
    # ``startup`` line says what each phase and each program's compile took.
    startup.report()
    monitor = ServeMonitorHook(batcher, every_steps=args.log_every)
    if args.loadgen_trace:
        try:
            return _drive_loadgen(args, engine, batcher, monitor,
                                  gateway=gateway, lifecycle=lifecycle)
        finally:
            if lifecycle is not None:
                lifecycle.close()
    futures: List[Any] = [None] * len(payloads)
    rejected = [0]
    lock = threading.Lock()
    stop = threading.Event()

    def client(cid: int) -> None:
        for i in range(cid, len(payloads), args.clients):
            if stop.is_set():
                return
            while True:
                try:
                    f = batcher.submit(payloads[i])
                    break
                except ServeOverloadedError:
                    with lock:
                        rejected[0] += 1
                    if stop.wait(args.batch_timeout_ms / 1000.0):
                        return
            with lock:
                futures[i] = f
            if (i + 1) % args.log_every == 0:
                monitor.log(i + 1)

    # Compile counter AFTER warm + batcher construction: everything the
    # timed window compiles on top of this is a warmup gap (and, under a
    # sampling mix, a one-program-set violation).
    compile_warm = engine.compile_stats()["compile_total"]
    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(max(1, args.clients))]
    for t in threads:
        t.start()
    interrupted = False
    try:
        # Join in short slices so a SIGTERM->KeyboardInterrupt (serve.py
        # installs the handler) lands HERE, not inside a blocking join.
        for t in threads:
            while t.is_alive():
                t.join(timeout=0.2)
    except KeyboardInterrupt:
        interrupted = True
        stop.set()
        logger.info(
            "interrupt: graceful drain — no new admissions, in-flight "
            "finish, queued shed (drain_timeout_s=%.1f)",
            args.drain_timeout_s)
        drain = getattr(batcher, "drain", None)
        if callable(drain):
            drain(args.drain_timeout_s)
        for t in threads:
            t.join(timeout=1.0)
    if interrupted:
        # Keep only the requests that finished before/during the drain;
        # shed ones raised ServeOverloadedError and are dropped here.
        results, done_payloads = [], []
        for i, f in enumerate(futures):
            if f is None or not f.done():
                continue
            try:
                results.append(f.result(timeout=0.0))
                done_payloads.append(payloads[i])
            except Exception:  # noqa: BLE001 — shed/failed mid-drain
                pass
    else:
        results = [f.result(timeout=600.0) for f in futures]
        done_payloads = payloads
    elapsed = time.perf_counter() - t0
    stats = batcher.stats()
    gstats = None
    if gateway is not None:
        gstats = gateway.stats()
        gateway.close(timeout=args.drain_timeout_s)
    batcher.close()
    monitor.log(len(payloads))
    if lifecycle is not None:
        lifecycle.close()

    completed = int(stats["completed"])
    out: Dict[str, Any] = {
        "model": args.model,
        "scheduler": ("continuous" if is_lm and args.continuous
                      else "fixed_batch"),
        "requests": args.steps,
        "completed": completed,
        "rejected_retries": rejected[0],
        "elapsed_s": round(elapsed, 4),
        "p50_latency_ms": round(stats["p50_latency_ms"], 3),
        "p99_latency_ms": round(stats["p99_latency_ms"], 3),
        "queue_wait_p50_ms": round(stats.get("queue_wait_p50_ms", 0.0), 3),
        "queue_wait_p99_ms": round(stats.get("queue_wait_p99_ms", 0.0), 3),
        "checkpoint_step": engine.restored_step,
        **_identity_keys(engine),
    }
    cstats = engine.compile_stats()
    out["programs_cached"] = int(cstats["programs_cached"])
    out["compile_total"] = int(cstats["compile_total"])
    out["compile_post_warmup"] = int(cstats["compile_total"] - compile_warm)
    if args.sampling_mix:
        out["sampling_mix"] = args.sampling_mix
        out["sampling_configs"] = len(
            sampling_lib.parse_sampling_mix(args.sampling_mix))
    if interrupted:
        out["drained"] = True
    if fleet:
        out["num_replicas"] = args.num_replicas
        out["fleet_dispatch"] = [
            int(stats.get(f"dispatch_replica_{i}", 0.0))
            for i in range(args.num_replicas)]
        out["fleet_shed"] = int(stats.get("shed", 0.0))
        out["fleet_redispatched"] = int(stats.get("redispatched", 0.0))
        out["param_generation"] = int(stats.get("param_generation", 0.0))
    if is_lm and args.continuous:
        out["slot_occupancy"] = round(stats["slot_occupancy"], 4)
        out["num_slots"] = int(stats["num_slots"])
        out["iterations"] = int(stats["iterations"])
        out["admissions_per_iter"] = round(stats["admissions_per_iter"], 3)
        out["retirements_per_iter"] = round(stats["retirements_per_iter"], 3)
        out["ttft_p50_ms"] = round(stats["ttft_p50_ms"], 3)
        out["ttft_p99_ms"] = round(stats["ttft_p99_ms"], 3)
        out["tpot_mean_ms"] = round(stats["tpot_mean_ms"], 4)
        out["tpot_p50_ms"] = round(stats.get("tpot_p50_ms", 0.0), 4)
        out["tpot_p99_ms"] = round(stats.get("tpot_p99_ms", 0.0), 4)
        out["cancelled"] = int(stats.get("cancelled", 0.0))
        out["ttfb_p50_ms"] = round(stats.get("ttfb_p50_ms", 0.0), 3)
        out["ttfb_p99_ms"] = round(stats.get("ttfb_p99_ms", 0.0), 3)
        out["prefill_budget"] = int(args.prefill_budget)
        out["prefill_chunks"] = int(stats.get("prefill_chunks", 0.0))
        out["megastep"] = int(args.megastep)
        out["megastep_auto"] = megastep_auto
        out["megastep_launches"] = int(stats.get("megastep_launches", 0.0))
        out["megastep_tokens"] = int(stats.get("megastep_tokens", 0.0))
        out["async_decode"] = bool(args.async_decode)
        out["device_idle_fraction"] = round(
            stats.get("device_idle_fraction", 0.0), 4)
        if args.async_decode:
            out["async_depth"] = int(args.async_depth)
            out["async_sync_fallbacks"] = int(
                stats.get("async_sync_fallbacks", 0.0))
            out["async_ring_depth_avg"] = round(
                stats.get("async_ring_depth_avg", 0.0), 3)
            out["async_fetch_wait_s"] = round(
                stats.get("async_fetch_wait_s", 0.0), 4)
        out["spec_k"] = int(args.spec_k)
        if args.spec_k:
            out["spec_launches"] = int(stats.get("spec_launches", 0.0))
            out["spec_drafted"] = int(stats.get("spec_drafted", 0.0))
            out["spec_accepted"] = int(stats.get("spec_accepted", 0.0))
            out["spec_emitted"] = int(stats.get("spec_emitted", 0.0))
            out["spec_acceptance_rate"] = round(
                stats.get("spec_acceptance_rate", 0.0), 4)
        out["slo_scheduling"] = bool(args.slo_scheduling)
        if args.slo_scheduling:
            out["preemptions_total"] = int(
                stats.get("preemptions_total", 0.0))
            out["preempt_swapped_total"] = int(
                stats.get("preempt_swapped_total", 0.0))
            out["preempt_recompute_total"] = int(
                stats.get("preempt_recompute_total", 0.0))
            out["resumes_total"] = int(stats.get("resumes_total", 0.0))
            out["swap_bytes_total"] = int(
                stats.get("swap_bytes_total", 0.0))
            out["deadline_met_total"] = int(
                stats.get("deadline_met_total", 0.0))
            out["deadline_missed_total"] = int(
                stats.get("deadline_missed_total", 0.0))
            out["deadline_goodput"] = round(
                stats.get("deadline_goodput", 0.0), 4)
        if lifecycle is not None:
            out.update(_lifecycle_keys(stats, args))
        out["cache_mode"] = args.cache_mode
        out["kv_dtype"] = args.kv_dtype or None
        if args.cache_mode == "paged":
            out["prefix_cache"] = bool(args.prefix_cache)
        if args.prefix_cache:
            out["prefix_hit_rate"] = round(stats["prefix_hit_rate"], 4)
            out["prefill_tokens_skipped"] = int(
                stats["prefill_tokens_skipped"])
            out["prefix_cached_blocks"] = int(stats["prefix_cached_blocks"])
            out["prefix_evictions"] = int(stats["prefix_evictions"])
        out["kv_hbm_bytes"] = int(stats["kv_hbm_bytes"])
        out["block_size"] = int(stats["block_size"])
        out["blocks_total"] = int(stats["blocks_total"])
        out["blocks_high_water"] = int(stats["blocks_high_water"])
        out["block_utilization"] = round(stats["block_utilization"], 4)
        out["blocks_per_request_mean"] = round(
            stats["blocks_per_request_mean"], 2)
        logger.info(
            "serve shutdown: cache_mode=%s%s kv=%.1fMiB blocks hw=%d/%d "
            "blk/req mean=%.1f",
            args.cache_mode,
            f" kv_dtype={args.kv_dtype}" if args.kv_dtype else "",
            out["kv_hbm_bytes"] / 2**20, out["blocks_high_water"],
            out["blocks_total"], out["blocks_per_request_mean"])
    else:
        out["avg_batch_occupancy"] = round(
            stats.get("avg_batch_occupancy", 0.0), 3)
        out["batches"] = int(stats.get("batches", 0))
    if gstats is not None:
        out["gateway_port"] = int(args.gateway_port)
        out["max_inflight"] = int(gstats["gateway_max_inflight"])
        out["gateway_accepted"] = int(gstats["gateway_accepted"])
        out["gateway_throttled"] = int(gstats["gateway_throttled"])
        out["gateway_cancel_requests"] = int(
            gstats["gateway_cancel_requests"])
        out["gateway_disconnects"] = int(gstats["gateway_disconnects"])
    if is_lm:
        delivered = int(sum(len(r) for r in results))
        out["tokens_generated"] = delivered
        out["tokens_per_sec"] = round(delivered / max(elapsed, 1e-9), 2)
        if not interrupted:
            # Submission-order digest of every generated stream: two runs
            # over the same traffic are token-identical iff these match
            # (the prefix-cache parity oracle).
            h = hashlib.sha256()
            for r in results:
                h.update(np.asarray(r, np.int32).tobytes())
            out["tokens_checksum"] = h.hexdigest()[:16]
        # Sanity surface for smoke tests: every delivered result honors
        # its horizon (a drained run only checks what actually finished).
        assert all(len(r) == _payload_parts(pl)[1]
                   for r, pl in zip(results, done_payloads))
    else:
        out["examples_per_sec"] = round(completed / max(elapsed, 1e-9), 2)
        out["predictions"] = results[: min(8, len(results))]
    return out
