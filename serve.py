#!/usr/bin/env python
"""Serving entrypoint — restore a checkpoint, serve batched inference.

Counterpart of ``train.py`` for the inference side: an in-process request
loop (synthetic clients -> DynamicBatcher -> ServeEngine) that prints ONE
JSON line of serve metrics (tokens/sec, latency percentiles, occupancy).

Examples:
    python serve.py --model=gpt2 --steps=32                  # fresh-init smoke
    python serve.py --model=gpt2 --checkpoint_dir=/tmp/ckpt --max_batch_size=8
    python serve.py --model=mnist --steps=64                 # classify path
    python serve.py --model=gpt2 --tensor=2                  # TP decode
    python serve.py --model=glm4_moe_lite --continuous --cache_mode=paged \
        --megastep=4 --async_decode     # latent (MLA) cache, sparse experts
    python serve.py --model=mellum --continuous --cache_mode=paged \
        --prefill_budget=64 --megastep=4  # window + full layers, two K/V pools
    python serve.py --model=glm_moe_dsa --continuous --cache_mode=paged \
        --prefill_budget=64 --megastep=4  # latent cache + index keys, sparse reads
    python serve.py --model=solar_open2 --continuous --cache_mode=paged \
        --prefill_budget=64 --megastep=4  # per-slot recurrent state + one K/V pool
    python serve.py --model=dots3_note --continuous --cache_mode=paged \
        --prefill_budget=64 --megastep=4  # window latent ring + selected latent + index keys
    python serve.py --model=gpt2 --continuous --num_slots=8 \
        --prompt_lens=8,16,24 --min_new_tokens=4             # continuous batching
    python serve.py --model=gpt2 --continuous --cache_mode=paged \
        --block_size=16 --kv_dtype=int8                      # paged + int8 KV
    python serve.py --model=gpt2 --continuous --cache_mode=paged \
        --prefix_cache --shared_prefix_len=256 \
        --shared_prefix_groups=4      # prefix caching over shared prompts
    python serve.py --model=gpt2 --continuous --prefill_budget=32 \
        --prompt_lens=8,8,8,512       # chunked prefill under whale prompts
    python serve.py --model=gpt2 --continuous --megastep=8 \
        --max_new_tokens=32           # K fused decode steps per dispatch
    python serve.py --model=gpt2 --continuous --async_decode \
        --megastep=auto               # double-buffered loop, autotuned K
    python serve.py --model=gpt2 --continuous --spec_k=4 \
        --prompt_period=4             # speculative decode, repetitive mix
    python serve.py --model=gpt2 --continuous \
        --sampling_mix=greedy:0.5,t0.8k40:0.3,t1.0p0.9:0.2 \
        --min_new_tokens=4    # per-request sampling, ONE program set
    python serve.py --model=gpt2 --continuous --metrics_port=9100 \
        --trace_out=/tmp/serve_trace.json   # scrape /metrics, dump a trace
    python serve.py --model=gpt2 --continuous --num_replicas=2 \
        --reload_poll_s=5 --checkpoint_dir=/tmp/ckpt  # fleet + hot reload
    python serve.py --model=gpt2 --continuous --gateway_port=8080 \
        --max_inflight=32     # HTTP/SSE front door + admission control
    python serve.py --model=gpt2 --continuous --cache_mode=paged \
        --slo_scheduling --num_blocks=24    # SLO tiers + KV swap-to-host
    python serve.py --model=gpt2 --continuous --cache_mode=paged \
        --slo_scheduling --loadgen_trace=poisson:n=64,rate=12 \
        --lifecycle_log=/tmp/lifecycle.jsonl  # open-loop goodput harness

SIGTERM (and Ctrl-C) triggers a graceful drain: no new admissions,
in-flight decodes finish (bounded by --drain_timeout_s), queued requests
are shed with backpressure errors.
"""

import argparse
import json
import logging
import signal
import threading


def _megastep_arg(value):
    # int K, or the literal "auto" (autotune K before the timed run).
    if value == "auto":
        return value
    try:
        return int(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--megastep takes an int >= 1 or 'auto', got {value!r}")


def parse_args(argv=None):
    from distributed_tensorflow_tpu.serve import ServeArgs

    defaults = ServeArgs()
    p = argparse.ArgumentParser(description="TPU-native batched serving")
    p.add_argument("--model", default=defaults.model,
                   help="gpt2, glm4_moe_lite, mellum, glm_moe_dsa, "
                        "solar_open2 or dots3_note (KV-cache decode) or "
                        "mnist|resnet50|bert (batched classify).  "
                        "glm4_moe_lite (latent attention, sparse experts), "
                        "mellum (grouped-query attention, window and "
                        "full layers over two paged K/V pools, sparse "
                        "experts) and glm_moe_dsa (latent attention over "
                        "the positions a learned indexer selects, index "
                        "keys in a second paged pool, sparse experts) "
                        "and solar_open2 (gated delta-rule linear "
                        "attention whose per-slot state lies beside one "
                        "grouped-query layer's paged K/V in four, sparse "
                        "experts) and dots3_note (window latent attention "
                        "in a ring pool of its own width beside latent "
                        "attention over an indexer's selection and its "
                        "index keys, head-wise output gates, sparse "
                        "experts) serve with --continuous "
                        "--cache_mode=paged only and refuse, with the "
                        "reason, --kv_dtype, --per_shard_kv, "
                        "--prefix_cache, --spec_k, --slo_scheduling and a "
                        "--tensor mesh")
    p.add_argument("--checkpoint_dir", default=None,
                   help="restore params from here (fresh random init when "
                        "unset or empty — the smoke path)")
    p.add_argument("--steps", type=int, default=defaults.steps,
                   help="number of requests to drive")
    p.add_argument("--max_batch_size", type=int,
                   default=defaults.max_batch_size)
    p.add_argument("--batch_timeout_ms", type=float,
                   default=defaults.batch_timeout_ms,
                   help="flush a partial batch after its oldest request "
                        "waited this long")
    p.add_argument("--max_queue_size", type=int,
                   default=defaults.max_queue_size,
                   help="admission control: pending requests past this "
                        "bound are rejected with backpressure")
    p.add_argument("--max_new_tokens", type=int,
                   default=defaults.max_new_tokens)
    p.add_argument("--min_new_tokens", type=int,
                   default=defaults.min_new_tokens,
                   help="when >0 and < max_new_tokens, per-request decode "
                        "horizons cycle between min and max (mixed traffic)")
    p.add_argument("--prompt_len", type=int, default=defaults.prompt_len)
    p.add_argument("--prompt_lens", default=defaults.prompt_lens,
                   help="comma-separated prompt lengths to cycle, e.g. "
                        "'8,16,24' (mixed traffic); empty = uniform "
                        "--prompt_len")
    p.add_argument("--clients", type=int, default=defaults.clients,
                   help="concurrent synthetic client threads")
    p.add_argument("--continuous", action="store_true",
                   default=defaults.continuous,
                   help="iteration-level decode scheduling over one "
                        "resident KV cache (serve/continuous.py) instead "
                        "of fixed request-level batches")
    p.add_argument("--num_slots", type=int, default=defaults.num_slots,
                   help="continuous mode: decode slots in the resident KV "
                        "cache (rounded up to the data-parallel row "
                        "multiple)")
    p.add_argument("--cache_mode", default=defaults.cache_mode,
                   choices=("dense", "paged"),
                   help="continuous mode KV layout: 'dense' keeps the "
                        "(num_slots, max_total_len) cache; 'paged' stores "
                        "K/V in a block pool through per-slot block tables")
    p.add_argument("--block_size", type=int, default=defaults.block_size,
                   help="paged mode: tokens per KV block")
    p.add_argument("--num_blocks", type=int, default=defaults.num_blocks,
                   help="paged mode: physical blocks in the pool (0 = full "
                        "capacity, no savings; smaller pools trade "
                        "admission backpressure for HBM)")
    p.add_argument("--kv_dtype", default=defaults.kv_dtype,
                   help="paged mode: KV storage dtype — '' stores the "
                        "compute dtype, 'int8' quantizes per token with "
                        "f32 scales, or any jnp dtype name ('bfloat16')")
    p.add_argument("--per_shard_kv", action="store_true",
                   default=defaults.per_shard_kv,
                   help="paged mode: partition the block pool over the "
                        "mesh's data shards — each shard owns "
                        "num_blocks/data blocks and slot tables index "
                        "only their own shard's range")
    p.add_argument("--prefix_cache", action="store_true",
                   default=defaults.prefix_cache,
                   help="paged mode: content-addressed prefix caching — "
                        "requests sharing full leading prompt blocks map "
                        "them from cache (refcounted, copy-on-write) and "
                        "prefill only the uncached suffix")
    p.add_argument("--prefill_budget", type=int,
                   default=defaults.prefill_budget,
                   help="continuous mode: max prompt tokens prefilled per "
                        "scheduler iteration — long prompts spread over "
                        "several iterations (chunked prefill) while "
                        "decoding slots keep stepping, so decode TPOT "
                        "never stalls behind a whale prompt; greedy "
                        "output is bit-identical (0 = one-shot prefill)")
    p.add_argument("--megastep", type=_megastep_arg,
                   default=defaults.megastep,
                   help="continuous mode: fuse this many decode iterations "
                        "into ONE compiled program (on-device lax.scan) — "
                        "one host dispatch + one fetch per K tokens; rows "
                        "finishing mid-megastep stop on device and trim on "
                        "host, so greedy output is bit-identical to "
                        "--megastep=1 (the classic per-token launch); "
                        "'auto' probes the dispatch/step-time ratio before "
                        "the timed run and pins the chosen K")
    p.add_argument("--async_decode", action="store_true",
                   default=defaults.async_decode,
                   help="continuous mode: run the decode loop ahead of the "
                        "host view — dispatch each launch before resolving "
                        "the previous ones (a ring --async_depth deep, "
                        "fetched on a dedicated thread), overlapping host "
                        "scheduling with device compute (up to depth-1 "
                        "iterations of delivery lag; greedy output is "
                        "bit-identical on vs off)")
    p.add_argument("--async_depth", type=int,
                   default=defaults.async_depth,
                   help="continuous mode with --async_decode: launches the "
                        "ring may hold in flight (1 = dispatch-then-"
                        "resolve, 2 = the classic double buffer, higher "
                        "rides out slower host iterations at more "
                        "delivery lag)")
    p.add_argument("--spec_k", type=int, default=defaults.spec_k,
                   help="continuous mode: speculative decoding — an "
                        "n-gram prompt-lookup drafter (no second model) "
                        "proposes up to k tokens per slot from the "
                        "slot's own history, verified in ONE "
                        "(num_slots, k+1) forward; greedy output is "
                        "bit-identical k on vs off (0 = off)")
    p.add_argument("--spec_ngram", type=int, default=defaults.spec_ngram,
                   help="speculative decoding: longest history n-gram "
                        "the drafter matches (backs off to 1)")
    p.add_argument("--slo_scheduling", action="store_true",
                   default=defaults.slo_scheduling,
                   help="continuous mode: rank admission by (priority "
                        "tier, deadline slack, arrival) instead of FIFO; "
                        "paged mode additionally preempts the lowest "
                        "tier under block pressure, swapping its KV to "
                        "host RAM (or recomputing) and resuming when "
                        "pressure clears")
    p.add_argument("--swap_min_tokens", type=int,
                   default=defaults.swap_min_tokens,
                   help="SLO scheduling: contexts shorter than this "
                        "always recompute on preemption instead of "
                        "swapping KV bytes to host")
    p.add_argument("--starvation_age_s", type=float,
                   default=defaults.starvation_age_s,
                   help="SLO scheduling: a waiting request gains one "
                        "effective priority tier per this many seconds, "
                        "so low tiers cannot starve forever")
    p.add_argument("--prompt_period", type=int,
                   default=defaults.prompt_period,
                   help="traffic mix: tile each prompt from a motif of "
                        "this many tokens instead of i.i.d. random — "
                        "the repetitive workload prompt-lookup drafting "
                        "wins on (0 = fully random)")
    p.add_argument("--shared_prefix_len", type=int,
                   default=defaults.shared_prefix_len,
                   help="traffic mix: prepend a shared system prompt of "
                        "this many tokens to every request (0 = off)")
    p.add_argument("--shared_prefix_groups", type=int,
                   default=defaults.shared_prefix_groups,
                   help="distinct shared prefixes the traffic cycles "
                        "through (with --shared_prefix_len)")
    p.add_argument("--num_replicas", type=int, default=defaults.num_replicas,
                   help=">1 serves a fleet: N replica engines behind a "
                        "load-aware router (requires --continuous)")
    p.add_argument("--reload_poll_s", type=float,
                   default=defaults.reload_poll_s,
                   help="fleet hot reload: poll --checkpoint_dir every "
                        "this many seconds and swap new steps in without "
                        "dropping in-flight requests (0 = off)")
    p.add_argument("--drain_timeout_s", type=float,
                   default=defaults.drain_timeout_s,
                   help="graceful-drain budget on SIGTERM/Ctrl-C: "
                        "in-flight requests get this long to finish")
    p.add_argument("--temperature", type=float, default=defaults.temperature,
                   help="sampling temperature; 0 = greedy argmax (default)")
    p.add_argument("--top_k", type=int, default=defaults.top_k,
                   help="restrict sampling to the k highest logits "
                        "(0 = full vocab); only with --temperature > 0")
    p.add_argument("--sampling_mix", default=defaults.sampling_mix,
                   help="per-request sampling mix (requires --continuous): "
                        "comma-separated <config>:<weight> entries where "
                        "<config> is 'greedy' or t<temp>/k<top_k>/p<top_p>/"
                        "a<presence>/f<frequency>/s<seed> runs, e.g. "
                        "'greedy:0.5,t0.8k40:0.3,t1.0p0.9:0.2' — every "
                        "config batches together in ONE compiled program "
                        "set ('' = uniform --temperature/--top_k)")
    p.add_argument("--preset", default=None,
                   help="config preset: gpt2 tiny|small|medium (default "
                        "tiny on CPU, medium on TPU); glm4_moe_lite "
                        "tiny|v5e8_share|flash (default tiny on CPU, "
                        "v5e8_share, one chip's share of an 8-chip "
                        "expert-parallel host, on TPU; flash is the whole "
                        "60 GB model); mellum tiny|v5e4_share|published "
                        "(default tiny on CPU, v5e4_share, one chip's "
                        "share of a 4-chip host, on TPU); glm_moe_dsa "
                        "tiny|v5e256_share|published (default tiny on "
                        "CPU, v5e256_share, one chip's share of a "
                        "v5e-256, on TPU); solar_open2 "
                        "tiny|v5e128_share|published (default tiny on "
                        "CPU, v5e128_share, one chip's share of a "
                        "v5e-128, on TPU); dots3_note "
                        "tiny|v5e256_share|published (default tiny on "
                        "CPU, v5e256_share, one chip's share of a "
                        "v5e-256, on TPU)")
    for axis in ("data", "fsdp", "tensor"):
        p.add_argument(f"--{axis}", type=int,
                       default=getattr(defaults, axis),
                       help=f"mesh size of the {axis!r} axis")
    p.add_argument("--log_every", type=int, default=defaults.log_every)
    p.add_argument("--seed", type=int, default=defaults.seed)
    p.add_argument("--metrics_port", type=int, default=defaults.metrics_port,
                   help="serve a Prometheus /metrics scrape endpoint on "
                        "this port for the run's lifetime (0 = off)")
    p.add_argument("--gateway_port", type=int, default=defaults.gateway_port,
                   help="bind the streaming HTTP gateway on this port for "
                        "the run's lifetime: POST /v1/generate (SSE "
                        "per-token streaming with stream=true), POST "
                        "/v1/cancel/<gid>, GET /v1/health|/v1/stats "
                        "(0 = off)")
    p.add_argument("--max_inflight", type=int, default=defaults.max_inflight,
                   help="gateway admission control: requests in flight "
                        "past this bound are answered 429 + Retry-After "
                        "instead of queueing unboundedly")
    p.add_argument("--priority_headroom", type=int,
                   default=defaults.priority_headroom,
                   help="gateway: >0 tiers the inflight gate — priority "
                        "p's limit is max_inflight - (9 - p) * headroom "
                        "(floored at 1), so under load the lowest tiers "
                        "shed (429) first (0 = single gate)")
    p.add_argument("--trace_out", default=defaults.trace_out,
                   help="write a Chrome trace-event JSON (per-request "
                        "queue/prefill/decode spans; load in Perfetto) "
                        "here at shutdown ('' = tracing off)")
    p.add_argument("--loadgen_trace", default=defaults.loadgen_trace,
                   help="open-loop load harness (requires --continuous): "
                        "an arrival-trace spec 'process:k=v,...' where "
                        "process is poisson|diurnal|burst and k=v pairs "
                        "override build_trace keywords, e.g. "
                        "'poisson:n=64,rate=12,whale_frac=0.2' — replaces "
                        "the closed-loop synthetic clients, counts 429s "
                        "as real shed, and reports goodput-under-SLO "
                        "('' = off)")
    p.add_argument("--arrival_rate", type=float,
                   default=defaults.arrival_rate,
                   help="mean arrival rate (req/s) for --loadgen_trace "
                        "specs that don't pin their own rate=")
    p.add_argument("--lifecycle_log", default=defaults.lifecycle_log,
                   help="attach the per-request lifecycle recorder and "
                        "stream its typed events (SUBMIT/ADMITTED/"
                        "FIRST_TOKEN/PREEMPTED/...) here as JSONL; the "
                        "JSON line gains per-phase breakdown keys "
                        "('' = off)")
    return ServeArgs(**vars(p.parse_args(argv)))


def _raise_interrupt(signum, frame):
    # Funnel SIGTERM into the KeyboardInterrupt path the driver already
    # handles: graceful drain instead of a hard kill.
    raise KeyboardInterrupt


def main(argv=None):
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s: %(message)s",
        force=True,
    )
    if threading.current_thread() is threading.main_thread():
        try:
            signal.signal(signal.SIGTERM, _raise_interrupt)
        except ValueError:
            pass  # embedded interpreter without signal support
    from distributed_tensorflow_tpu import compile_cache
    from distributed_tensorflow_tpu.serve import run_serve

    args = parse_args(argv)
    compile_cache.configure()
    result = run_serve(args)
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
