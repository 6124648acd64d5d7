"""Continuous batching: Orca-style iteration-level decode scheduling.

The fixed-batch path (``ServeEngine.generate`` behind ``DynamicBatcher``)
batches at REQUEST granularity: every row in a flushed batch decodes for the
full shared horizon before any result returns, and newly-arrived requests
wait for the whole batch to drain.  ``ContinuousScheduler`` re-forms the
batch every decode step instead (Yu et al., OSDI 2022 — PAPERS.md):

- ONE resident KV cache of shape ``(num_slots, max_total_len)`` lives for
  the scheduler's lifetime (``ServeEngine.init_slot_cache``); requests are
  admitted into free slots and retired out of them mid-flight, vLLM-style
  slot/cache reuse discipline (Kwon et al., SOSP 2023).
- Each iteration: (a) ADMIT queued requests into free slots via slot-local
  prefill (``prefill_into_slots`` resets the slot's index rows and writes
  the prompt's K/V at that slot's rows — stale K/V from the previous
  occupant stays masked behind the reset index); (b) run ONE decode
  launch over all slots (``decode_megastep``: ``megastep`` fused steps,
  one by default) with an active-mask so empty slots are free compute;
  (c) RETIRE slots whose row hit its eos token or its per-request
  ``max_new_tokens``, resolving that request's Future immediately — no
  request ever waits on another's horizon.

Completion is out of submission order by design.  The per-request metrics
this unlocks — time-to-first-token (submit -> prefill token) and
time-per-output-token (decode cadence) — are first-class in ``stats()``,
exported by ``obs.ServeMonitorHook``.

Admission control mirrors ``DynamicBatcher``: a bounded queue that rejects
with ``ServeOverloadedError`` instead of growing tail latency unboundedly.

Fleet extensions (``serve/fleet``):

- HOT WEIGHT RELOAD — ``update_params`` stages a new generation-tagged
  params tree; the loop swaps it in at the top of its next iteration.
  Requests pin the generation current at ADMISSION (``_ParamGeneration``
  refcount), in-flight decodes finish on the weights they started with
  (the iteration groups rows by generation, one ``decode_megastep`` call
  per live generation — normally exactly one), and a superseded generation's
  params are dropped when its refcount drains to zero.  Each resolved
  Future carries its ``generation`` tag.
- PER-SHARD KV POOLS — ``per_shard_kv=True`` (paged mode) partitions the
  block pool over the mesh's data axis: the device pools shard their
  block dim (``gpt2_cache_rules(per_shard_pools=True)``), the allocator
  partitions block ids contiguously per shard, and every slot is pinned
  to the data shard its rows live on — block tables only ever index local
  blocks, so per-device KV HBM drops by the data-axis width.
- GRACEFUL DRAIN — ``drain()`` stops admissions (submit sheds with
  ``ServeOverloadedError``), fails the queued-but-unadmitted backlog, and
  waits for every resident slot to finish before the caller ``close()``s.
- PREFIX CACHING — ``prefix_cache=True`` (paged mode) maps a new
  request's longest cached prompt prefix straight into its block table
  (refcounted shares of blocks other slots already filled; see
  ``serve/paged.py`` for the chained-hash/COW invariants) and prefills
  only from the first uncached token via the engine's ``start_offsets``
  path — admission skips the shared prefix's compute AND its HBM.
  Composes with per-shard pools (each shard keys its own map — slots
  only index local blocks) and hot reload (the map is invalidated at
  generation install: cached K/V is params-dependent).
- CHUNKED PREFILL — ``prefill_budget > 0`` bounds the prompt tokens
  prefilled per iteration: a request whose remaining prompt exceeds the
  budget is admitted into its slot but prefills one
  ``min(remaining, budget)``-token chunk per iteration
  (``prefill_into_slots(start_offsets=...)`` — chunk N starts where
  chunk N-1 stopped; the last chunk may be ragged), so a whale prompt
  never stalls the resident decode slots for more than one budget's
  worth of prefill compute.  Slots mid-prefill are excluded from the
  decode step's active mask; the FINAL chunk's output is the request's
  first generated token (earlier chunks' outputs predict prompt tokens
  the caller already has), which is where TTFT is stamped.  Chunking is
  a pure scheduling change: the same K/V lands at the same positions,
  so greedy output is bit-identical budget on vs off.  Prefix-cached
  prompt tokens cost ZERO budget — the chunk walk starts past the
  mapped blocks.  The walk serves not-yet-started requests first (one
  small chunk starts a short prompt decoding; the whale's remaining
  chunks overlap it), with an aging bound (``_PREFILL_AGE_LIMIT``) so
  sustained short traffic can't starve an in-progress whale.
  ``prefill_budget=0`` (default) keeps the one-shot whole-prompt
  prefill.
- MEGASTEP DECODE — every plain decode launch is a megastep:
  ``megastep K`` fuses K decode iterations (default 1) into ONE
  compiled program (``engine.decode_megastep``: a bounded
  ``lax.while_loop`` over the inner step that ALSO exits early once
  every row is dead, so an all-eos megastep stops paying for its
  remaining masked no-op steps) so the host pays one dispatch + one
  fetch per K tokens instead of per token.  Slot decode state rides the device
  between inner steps: sampling folds the same per-token counters in on
  device, a row that hits its eos or horizon at inner step j < K stops
  advancing there (its index rows gate exactly like the single-step
  active mask; the host trims its tail columns), and paged block
  tables are precomputed for all K positions at megastep start
  (``_ensure_blocks`` covers ``len(prompt)+len(tokens)+K-1`` once,
  clamped to the admission reservation).  The scheduler admits and
  retires only at megastep boundaries; ``toks`` come back as one
  ``(num_slots, K)`` fetch.  Greedy output is bit-identical K on vs
  off — megastep is a pure dispatch-granularity change, the same
  scheduling-only contract as chunked prefill.  TPOT attribution for
  K > 1 anchors to the launch's own device window: the on-device
  iteration clock reports how many inner steps actually ran, the
  realized cadence is (fetch - dispatch) / steps_run, and a row's j-th
  token is stamped dispatch + (j+1) cadences — intra-megastep spread
  is flattened, but the cadence is the device's, not a share of the
  host's observation gap (which, async, spans an iteration of host
  work).  ``megastep="auto"`` defers the choice of K to the scheduler:
  it samples dispatch cost and per-inner-step device time, picks the
  smallest power of two with dispatch <= K * step / 2 (clamped to
  [1, 32]) once both deques hold enough samples, and FREEZES — K is
  compiled-program identity, so it is chosen once, not chased.
- SPECULATIVE DECODING — ``spec_k >= 1`` turns each decode iteration
  into draft-and-verify: an n-gram prompt-lookup drafter (NO second
  model — the last up-to-``spec_ngram`` tokens of each slot's own
  prompt+output history are matched against that history's earlier
  occurrences, and the continuation after the latest match proposes up
  to ``spec_k`` draft tokens) feeds ONE ``(num_slots, spec_k+1)``
  verify forward (``engine.verify_slots``) that scores the last token
  plus every draft in a single launch.  Each row keeps its longest
  draft prefix that agrees with the per-position target tokens plus
  one bonus/correction target — between 1 and ``spec_k + 1`` tokens
  per launch per slot — and its ``cache_index``/``position`` advance
  by exactly the kept length (per-slot variable advance; rejected
  drafts' K/V stays masked behind the rolled-back index).  Greedy
  targets are the exact greedy tokens, so greedy output is
  bit-identical spec on vs off (the standing parity oracle); sampled
  targets are drawn with the SAME per-token ``fold_in`` counters the
  sequential loop would burn (unconsumed counters are refunded after
  the launch), so sampled output stays distribution-exact — with
  single-stream traffic, token-identical spec on vs off.  Iterations
  where NO slot has a draft fall through to the plain megastep
  dispatch — a degenerate k=0 verify program is never built; slots
  without a draft in a drafting
  iteration ride the verify launch with ``draft_len 0`` and advance by
  one token, exactly a plain decode step.  Composes with chunked
  prefill (prefilling slots are inactive-masked as ever), prefix
  caching (drafts only read host history; block coverage clamps to the
  admission reservation via ``spec_coverage``) and hot reload (one
  verify launch per pinned generation).  The win is fewer sequential
  launches per generated token on repetitive/structured text —
  ``spec_emitted / spec_launches`` tokens per launch against the plain
  path's one.
- DEEP ASYNC DECODE — every launch has a dispatch and a fetch half and
  goes through one bounded LAUNCH RING: each iteration dispatches
  launch N, then resolves the oldest ring records until at most D-1
  stay in flight.  Without ``async_decode`` D is 1 — dispatch, then
  resolve: the synchronous loop.  ``async_decode=True`` makes D
  ``async_depth`` (default 2 — the classic double buffer), so the
  device runs up to D launches ahead of the host view and admission,
  prefill chunking,
  and retirement bookkeeping all overlap executing compute.  Records
  resolve strictly in launch order; a dedicated FETCH THREAD performs
  the ``jax.device_get`` half off the loop thread (a device_get is
  not a launch — it needs no launch lock), handing host arrays back
  through each record's Future, so fetch latency overlaps the next
  iteration's host scheduling too.  The donated resident cache makes
  the chain safe: every launch rebinds the cache in the assignment
  that donates it, the next dispatch consumes device values (token
  carry + cache) with no host round-trip, and all host syncs route
  through ``_fetch_host`` (the one sanctioned ``jax.device_get``) —
  the discipline dttlint's ``use-after-donate``/``host-sync`` rules
  machine-check.  The cost is up to D-1 iterations of delivery lag: a
  request submitted while launch N is in flight prefills at N+1 (its
  final chunk's first-token fetch rides the ring as a deferred
  record), and its first decoded tokens land when that record
  resolves.  A slot admitted mid-flight has its true last token only
  on host, so dispatch passes per-slot ``fresh_tokens``/``fresh``
  vectors and the launch's first step selects them on device.
  Speculative decoding COMPOSES: drafts build from the stale fetched
  view and a chain-verify launch scores them against the
  device-resident carry, so staleness costs acceptance length, never
  a token.  Only seeded-sampling and mixed-generation iterations
  still drain the ring and run at depth 1
  (``async_sync_fallbacks`` counts them).  Greedy output is
  bit-identical async on vs off at every depth; the observable win is
  ``device_idle_fraction`` (share of the window with no launch in
  flight, from the dispatch/fetch spans) going to ~zero on
  decode-heavy traffic.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import queue
import threading
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import numpy as np

from distributed_tensorflow_tpu.models import PagedKVConfig
from distributed_tensorflow_tpu.obs import metrics as obs_metrics
from distributed_tensorflow_tpu.obs.lifecycle import EMPTY_LIFECYCLE_STATS
from distributed_tensorflow_tpu.obs.trace import (
    default_tracer,
    now as _now,
    spanned,
)
from distributed_tensorflow_tpu.ops import grouped_matmul
from distributed_tensorflow_tpu.ops.paged_attention import KERNEL_PATHS
from distributed_tensorflow_tpu.serve.batcher import (
    ServeOverloadedError,
    _percentile,
    _serve_instruments,
)
from distributed_tensorflow_tpu.serve import sampling as sampling_lib
from distributed_tensorflow_tpu.serve.paged import (
    BlockAllocator,
    chain_block_keys,
    megastep_coverage,
    spec_coverage,
)
from distributed_tensorflow_tpu.serve.tiering import HostKVPool, SwapPolicy

logger = logging.getLogger(__name__)

# Chunked prefill: iterations a prefill-pending slot may go chunk-less
# (budget spent on other slots) before it jumps the walk order — bounds
# an in-progress whale's wait under sustained new-short-prompt traffic.
_PREFILL_AGE_LIMIT = 4

# Megastep autotune (``megastep='auto'``): evaluate the dispatch/step
# timing ratio every this many iterations (the slow control loop), with
# at least this many samples of each before committing.  The first
# confident pick FREEZES — compiled-program identity must stay stable
# once traffic is flowing, so autotune trades a late optimum for zero
# steady-state recompiles.
_AUTOTUNE_EVERY = 16
_AUTOTUNE_MIN_SAMPLES = 8
_AUTOTUNE_MAX_K = 32

# Trace lane (``tid``) of the ``launch`` spans, dispatch to fetch-done: they
# overlap the loop's own spans on lane 0, so they get a lane of their own.
_LAUNCH_LANE = -1


@dataclasses.dataclass
class _Turnover:
    """A slot between two requests: opened at the retirement of one with
    another waiting in the queue, stamped as the successor is admitted
    and as its prefill launch ends, closed by the first decode launch
    that carries the successor (the ``slot_turnover`` span)."""
    retired_at: float
    rid_out: int
    queued_at_retire: int
    rid_in: Optional[int] = None
    admitted_at: Optional[float] = None
    prefilled_at: Optional[float] = None


def _continuous_instruments(registry=None):
    """The iteration-level families on top of the shared serve set."""
    r = registry or obs_metrics.default_registry()
    out = _serve_instruments(r)
    out.update({
        "admissions": r.counter(
            "dtt_serve_admissions_total", "Requests admitted into slots"),
        "retirements": r.counter(
            "dtt_serve_retirements_total", "Slots retired"),
        "ttft": r.histogram(
            "dtt_serve_ttft_seconds", "Submit to first generated token"),
        "tpot": r.histogram(
            "dtt_serve_tpot_seconds", "Per-output-token decode cadence",
            buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                     0.025, 0.05, 0.1, 0.25, 0.5, 1.0)),
        "request": r.histogram(
            "dtt_serve_request_seconds", "Submit to retirement"),
        "slot_turnover": r.histogram(
            "dtt_serve_slot_turnover_seconds",
            "Retirement of a slot's request, with the queue non-empty, to "
            "the first decode launch that carries its successor"),
        "active_slots": r.gauge(
            "dtt_serve_active_slots", "Slots currently decoding"),
        "prefix_hits": r.counter(
            "dtt_kv_prefix_hits_total",
            "Cacheable prompt blocks served from the prefix cache"),
        "prefix_misses": r.counter(
            "dtt_kv_prefix_misses_total",
            "Cacheable prompt blocks that had to be prefilled"),
        "prefix_skipped": r.histogram(
            "dtt_kv_prefix_prefill_tokens_skipped",
            "Prompt tokens whose prefill compute a cache hit skipped",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048)),
        "prefill_chunk": r.histogram(
            "dtt_serve_prefill_chunk_tokens",
            "Prompt tokens prefilled per chunk (chunked prefill)",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048)),
        "prefill_backlog": r.gauge(
            "dtt_serve_prefill_backlog_tokens",
            "Prompt tokens admitted into slots but not yet prefilled"),
        "prefilling_slots": r.gauge(
            "dtt_serve_prefilling_slots",
            "Slots admitted but still prefilling their prompt"),
        "megastep_size": r.histogram(
            "dtt_serve_megastep_size",
            "Inner decode steps fused per compiled decode launch",
            buckets=(1, 2, 4, 8, 16, 32, 64)),
        "moe_assignments": r.counter(
            "dtt_serve_moe_assignments_total",
            "Router choices made in decode launches, by whether the chosen "
            "expert is held on this device ('here') or on another chip of "
            "the expert-parallel deployment ('absent'); counted on the "
            "device by the expert layers and fetched with the tokens",
            labelnames=("held",)),
        "kv_blocks_held": r.gauge(
            "dtt_serve_kv_blocks_held",
            "Physical K/V blocks live requests hold, by the kind of pool: "
            "'full' (the block-table pool, grows with the row) or 'window' "
            "(a family's window layers: at most the slot's ring); "
            "'latent' and 'index' where a family keeps index keys in a "
            "second pool under the latent pool's block table (a block of "
            "the table is one of each)",
            labelnames=("kind",)),
        "state_bytes_held": r.gauge(
            "dtt_serve_state_bytes_held",
            "Bytes of per-slot recurrent state (a family's linear-attention "
            "layers: whatever the row's length) that live and prefilling "
            "slots hold"),
        "state_resets": r.counter(
            "dtt_serve_state_resets_total",
            "Admissions whose first prefill chunk started a slot's "
            "recurrent state from zero"),
        "window_recycled": r.counter(
            "dtt_serve_window_blocks_recycled_total",
            "Blocks of a row's positions written over a ring entry whose "
            "positions had slid out of the window (no block was taken)"),
        "megastep_amortized": r.counter(
            "dtt_serve_megastep_launches_amortized_total",
            "Tokens fetched beyond one per decode launch (host "
            "dispatches the megastep/batch amortized away)"),
        "spec_drafted": r.counter(
            "dtt_serve_spec_drafted_total",
            "Draft tokens proposed by the n-gram prompt-lookup drafter"),
        "spec_accepted": r.counter(
            "dtt_serve_spec_accepted_total",
            "Draft tokens accepted by the k-token verify step"),
        "spec_accept_rate": r.histogram(
            "dtt_serve_spec_acceptance_rate",
            "Per-verify-launch fraction of drafted tokens accepted",
            buckets=(0.0, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0)),
        "spec_accepted_len": r.histogram(
            "dtt_serve_spec_accepted_tokens",
            "Tokens emitted per slot per verify launch (accepted "
            "drafts + the bonus/correction token)",
            buckets=(1, 2, 3, 4, 6, 8, 12, 16, 32)),
        "device_idle": r.gauge(
            "dtt_serve_device_idle_fraction",
            "Fraction of the decode window the device sat with NO "
            "launch in flight (gap between a fetch completing and the "
            "next dispatch) — async decode's target"),
        "ring_depth": r.gauge(
            "dtt_serve_async_ring_depth",
            "Launches in the async ring right now (post-dispatch "
            "occupancy; bounded by --async_depth)"),
        "ttfb": r.histogram(
            "dtt_serve_ttfb_seconds",
            "Submit to first token DELIVERED off the loop thread "
            "(streaming time-to-first-byte; TTFT plus the emit hop)"),
        "cancelled": r.counter(
            "dtt_serve_cancelled_total",
            "Requests cancelled by the client (queued or mid-decode)"),
        "preemptions": r.counter(
            "dtt_serve_preemptions_total",
            "Requests evicted from their slot under block pressure "
            "(SLO scheduling)"),
        "resumes": r.counter(
            "dtt_serve_resumes_total",
            "Preempted requests re-admitted (swap-restore or recompute)"),
        "swap_out_bytes": r.counter(
            "dtt_kv_swap_out_bytes_total",
            "KV bytes moved device -> host at preemption"),
        "swap_in_bytes": r.counter(
            "dtt_kv_swap_in_bytes_total",
            "KV bytes moved host -> device at resume"),
        "deadline_met": r.counter(
            "dtt_serve_deadline_met_total",
            "Completed requests whose TTFT met their deadline_ms"),
        "deadline_missed": r.counter(
            "dtt_serve_deadline_missed_total",
            "Completed requests whose TTFT missed their deadline_ms"),
    })
    return out


@dataclasses.dataclass
class _SlotRequest:
    """Per-slot state for one in-flight request."""

    prompt: np.ndarray
    max_new_tokens: int
    eos_token: Optional[int]
    future: Future
    submitted: float                 # obs.trace.now() at submit
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1
    # Paged mode: worst-case blocks admission reserved for this request
    # that have NOT been physically allocated yet (released as the slot's
    # length crosses block boundaries, or at retirement).
    reserved_blocks: int = 0
    # Tracing: request id (the trace's tid — one Perfetto lane per
    # request) and when this request, at head of line, first failed paged
    # block admission (the reservation-wait span's start).
    rid: int = 0
    blocked_since: Optional[float] = None
    # Hot reload: the param generation pinned at admission (the request
    # decodes on these weights even if a newer generation lands mid-flight).
    gen: Optional["_ParamGeneration"] = None
    # Per-request sampling config (frozen SamplingParams; None only before
    # submit fills it in).  Rides into every launch as one row of the
    # runtime parameter vectors — never a compile-cache key.
    sampling: Optional[sampling_lib.SamplingParams] = None
    # Prefix caching: the prompt's chained block content keys, computed
    # once on the submitting thread (pure hashing — no allocator state).
    prefix_keys: List[bytes] = dataclasses.field(default_factory=list)
    # Chunked prefill (loop-thread state): the next prompt position to
    # prefill (admission sets it to the prefix-mapped start; the request
    # is still PREFILLING while it is short of the prompt length), how
    # many chunks have run, when the first chunk started, and how many
    # leading tokens the prefix cache mapped (zero budget spent on them).
    next_prefill_offset: int = 0
    prefill_chunks: int = 0
    prefill_started_at: Optional[float] = None
    prefix_cached: int = 0
    # When this request's latest token landed (first set at the final
    # prefill chunk) — each decode step's now - last_token_at is one
    # inter-token gap sample.
    last_token_at: Optional[float] = None
    # Iterations this slot sat prefill-pending without receiving a chunk
    # (budget spent on other slots); at _PREFILL_AGE_LIMIT the slot jumps
    # the chunk queue so a whale can't starve behind a stream of new
    # short prompts.
    prefill_idle: int = 0
    # Streaming: the per-token delivery callback (``submit(on_token=)``),
    # how many of ``tokens`` have been handed to it, and whether the
    # client cancelled.  ``cancelled`` is read and written ONLY under the
    # scheduler lock (set by ``cancel()`` on a client thread, honoured by
    # the loop at its next iteration boundary); ``streamed`` advances
    # under the lock too so a cancel can never lose or double a delivery.
    on_token: Optional[Any] = None
    streamed: int = 0
    cancelled: bool = False
    # SLO scheduling: the ORIGINAL prompt length — the recompute resume
    # path folds already-emitted tokens into ``prompt`` (re-prefill of
    # the full history recreates the preempted K/V exactly), so every
    # written-position computation must anchor to the BASE length, never
    # ``len(prompt)`` — and how many times this request was preempted.
    base_prompt_len: int = -1
    preemptions: int = 0

    def __post_init__(self):
        if self.base_prompt_len < 0:
            self.base_prompt_len = len(self.prompt)

    def prefilling(self) -> bool:
        return self.next_prefill_offset < len(self.prompt)

    def chunk_priority(self) -> Tuple[bool, bool, int]:
        """Sort key for the per-iteration chunk walk (lower = first).

        Not-yet-started requests outrank in-progress ones: a new short
        prompt needs ONE small chunk to begin decoding, while an
        in-progress whale only moves its own (already bounded) first
        token closer — so overlapping the shorts with the whale's
        remaining chunks is pure throughput.  An in-progress slot that
        has sat ``_PREFILL_AGE_LIMIT`` iterations without a chunk jumps
        the queue, so sustained short traffic can't starve a whale.
        Ties resolve oldest request first (deterministic)."""
        return (self.prefill_idle < _PREFILL_AGE_LIMIT,
                self.prefill_chunks > 0, self.rid)

    def done(self) -> bool:
        if len(self.tokens) >= self.max_new_tokens:
            return True
        return (self.eos_token is not None and len(self.tokens) > 0
                and self.tokens[-1] == self.eos_token)

    def max_written_tokens(self) -> int:
        """Most K/V positions this request can ever write: the BASE
        prompt plus one per decode step (the last generated token never
        re-enters the cache).  Anchored to ``base_prompt_len`` — after a
        recompute resume, ``prompt`` holds prompt + emitted tokens, but
        the physical ceiling never moves."""
        return self.base_prompt_len + self.max_new_tokens - 1


@dataclasses.dataclass
class _InflightMegastep:
    """One dispatched-but-not-fetched megastep launch (async decode).

    Everything the fetch half needs to resolve the launch LATER, after
    the host has run admission/prefill/retirement against the previous
    iteration's results: the per-generation launch outputs (device
    handles — touched only through ``jax.device_get``), a snapshot of
    which requests were decoding (and how far along each was) at
    dispatch, and the per-slot token counts the dispatch already charged
    (``pending``) so the next dispatch's horizons exclude tokens that
    are still in flight.

    Records live in the scheduler's launch ring (``async_depth`` deep)
    and resolve strictly in launch order.  The fetch thread performs the
    ``jax.device_get`` half and hands the HOST arrays back through
    ``fetched`` — the one cross-thread handoff; every plain field is
    written at construction on the loop thread and only read afterwards.
    """

    # [(slots, toks_dev, steps_dev)] — one entry per live generation.
    launches: List[Tuple[List[int], Any, Any]]
    # slot -> _SlotRequest snapshot at dispatch (same objects as
    # self._active; membership frozen at dispatch).
    decoding: Dict[int, Any]
    # slot -> prior len(req.tokens) at dispatch (columns before this
    # launch's output — includes every OLDER ring record's pending).
    base_len: Dict[int, int]
    # slot -> tokens this launch can still emit (min(K, horizon)); the
    # NEXT dispatch subtracts these (summed over the whole ring) from
    # its own horizons.
    pending: Dict[int, int]
    steps: int                       # the K this launch compiled with
    dispatch_t: float                # obs.trace.now() at dispatch
    seq: int                         # _launch_seq at dispatch
    clock_dev: Any = None            # on-device iteration clock output
    # Device handles the fetch thread resolves (set at construction):
    # (launches, clock_dev, expert counts) — one ``jax.device_get`` over
    # the pytree.
    fetch_payload: Any = None
    # True once handed to the fetch thread; resolution then reads
    # ``fetched`` instead of fetching inline.
    enqueued: bool = False
    # Resolved by the fetch thread to (host pytree, fetch-done time).
    fetched: Future = dataclasses.field(default_factory=Future)


@dataclasses.dataclass
class _InflightSpec:
    """One dispatched-but-not-fetched speculative verify launch (async
    decode + ``spec_k``).  Drafts were built from the N-1 fetched host
    view — staleness only costs acceptance, never correctness: the
    verify scores against the device-resident carry, so the emitted
    targets are the exact sequential tokens regardless of what the host
    had seen at draft time."""

    # [(slots, targets_dev, accepted_dev)] — single generation only
    # (mixed generations fall back to sync).
    launches: List[Tuple[List[int], Any, Any]]
    decoding: Dict[int, Any]
    # slot -> WORST-CASE tokens this launch may emit (draft_len + 1,
    # clamped to the horizon); later dispatches budget against it and
    # the resolve trues the host view up.
    pending: Dict[int, int]
    draft_lens: Dict[int, int]       # slot -> real (unpadded) draft len
    k: int                           # the spec_k the program compiled with
    dispatch_t: float
    seq: int
    clock_dev: Any = None
    fetch_payload: Any = None
    enqueued: bool = False
    fetched: Future = dataclasses.field(default_factory=Future)


@dataclasses.dataclass
class _InflightPrefill:
    """One final prefill chunk whose first-token fetch was deferred into
    the launch ring (async decode): the chunk's launch interleaves with
    in-flight decode fetches instead of serializing the loop thread on a
    blocking ``device_get`` mid-iteration.  The slot stays out of the
    decode-active set (``req.tokens`` empty) until this resolves."""

    req: Any                         # the _SlotRequest mid-handoff
    dispatch_t: float                # final chunk launch time
    pending: Dict[int, int] = dataclasses.field(default_factory=dict)
    fetch_payload: Any = None        # tok_dev — (1,) first decoded token
    enqueued: bool = False
    fetched: Future = dataclasses.field(default_factory=Future)


@dataclasses.dataclass
class _ParamGeneration:
    """One weight generation: a sharded params tree, its checkpoint-step
    tag, and a refcount of in-flight requests pinned to it.  The scheduler
    mutates ``refs`` only under its lock; when a SUPERSEDED generation's
    refcount drains to zero its ``params`` reference is dropped so the
    device buffers actually free."""

    params: Any
    generation: int
    refs: int = 0


class ContinuousScheduler:
    """Persistent decode loop owning one resident KV cache.

    ``submit`` enqueues a request and returns a Future resolving to its
    1-D generated-token array (ending at its eos token if one was hit).
    One scheduler thread runs admit -> decode -> retire iterations for the
    scheduler's lifetime; it sleeps only while no request is active or
    queued.

    ``num_slots`` is rounded up to the engine's bucketed shapes (a
    multiple of the mesh's data-parallel extent — slot rows shard over the
    data axes).  ``max_total_len`` bounds prompt + generated length per
    slot; admission validates it per request.

    ``prefill_budget > 0`` caps the prompt tokens prefilled per iteration
    (chunked prefill — see the module docstring): long prompts prefill in
    ``min(remaining, budget)``-token chunks interleaved with the decode
    step instead of stalling it for one whole-prompt prefill.  Greedy
    output is bit-identical budget on vs off.
    """

    @spanned("scheduler_init", "startup")   # up to the loop thread running
    def __init__(
        self,
        engine,
        *,
        num_slots: int = 8,
        max_total_len: Optional[int] = None,
        max_queue_size: int = 64,
        eos_token: Optional[int] = None,
        temperature: float = 0.0,
        top_k: int = 0,
        cache_mode: str = "dense",
        block_size: int = 16,
        num_blocks: Optional[int] = None,
        kv_dtype: Optional[str] = None,
        per_shard_kv: bool = False,
        prefix_cache: bool = False,
        prefill_budget: int = 0,
        megastep: Union[int, str] = 1,
        async_decode: bool = False,
        async_depth: int = 2,
        spec_k: Optional[int] = None,
        spec_ngram: int = 3,
        slo_scheduling: bool = False,
        swap_min_tokens: int = 32,
        starvation_age_s: float = 5.0,
        lifecycle=None,
        name: str = "serve-continuous",
        start: bool = True,
    ):
        cfg = getattr(engine.module, "cfg", None)
        if cfg is None:
            raise ValueError(
                "ContinuousScheduler serves the KV-cache decode path; "
                f"model {engine.model!r} has no decode cache")
        if cache_mode not in ("dense", "paged"):
            raise ValueError(
                f"cache_mode must be 'dense' or 'paged', got {cache_mode!r}")
        if cache_mode == "dense" and kv_dtype is not None:
            raise ValueError(
                "kv_dtype applies to cache_mode='paged' only (the dense "
                "cache stores the model's compute dtype)")
        if per_shard_kv and cache_mode != "paged":
            raise ValueError(
                "per_shard_kv partitions the paged block pool — it "
                "requires cache_mode='paged'")
        if prefix_cache and cache_mode != "paged":
            raise ValueError(
                "prefix_cache shares physical KV blocks through block "
                "tables — it requires cache_mode='paged'")
        if prefill_budget < 0:
            raise ValueError(
                f"prefill_budget must be >= 0 (0 = unchunked one-shot "
                f"prefill), got {prefill_budget}")
        # What this model's family cannot serve yet is refused here, with
        # the family's own reason, never fallen back from in silence.
        asked = {
            "dense_cache": cache_mode == "dense",
            "kv_dtype": kv_dtype is not None,
            "per_shard_kv": bool(per_shard_kv),
            "slo_scheduling": bool(slo_scheduling),
            "spec_k": bool(spec_k),
            "prefix_cache": bool(prefix_cache),
        }
        for feature, reason in engine.workload.serve_refusals.items():
            if asked.get(feature):
                raise ValueError(
                    f"model {engine.model!r} cannot be served with "
                    f"{feature}: {reason}")
        self.megastep_auto = False
        if isinstance(megastep, str):
            if megastep != "auto":
                raise ValueError(
                    f"megastep must be an int >= 1 or 'auto' (autotune K "
                    f"from the observed dispatch/step-time ratio), got "
                    f"{megastep!r}")
            # Autotune starts at the classic K=1 launch and re-evaluates
            # on a slow control loop; once enough timing samples land the
            # chosen K FREEZES so compiled-program identity stays stable.
            self.megastep_auto = True
            megastep = 1
        elif megastep < 1:
            raise ValueError(
                f"megastep must be >= 1 (1 = one decode iteration per "
                f"compiled launch, the classic path), got {megastep}")
        if async_depth < 1:
            raise ValueError(
                f"async_depth must be >= 1 (launches the ring may hold "
                f"in flight; 1 = dispatch-then-resolve, 2 = the classic "
                f"double buffer), got {async_depth}")
        if spec_k is not None and spec_k < 1:
            raise ValueError(
                f"spec_k must be >= 1 when set (None/unset disables "
                f"speculative decoding; a k=0 verify would just be the "
                f"plain decode step), got {spec_k}")
        if spec_ngram < 1:
            raise ValueError(
                f"spec_ngram must be >= 1 (longest history n-gram the "
                f"prompt-lookup drafter matches), got {spec_ngram}")
        if swap_min_tokens < 0:
            raise ValueError(
                f"swap_min_tokens must be >= 0 (contexts shorter than "
                f"this recompute instead of swapping), got "
                f"{swap_min_tokens}")
        if starvation_age_s <= 0:
            raise ValueError(
                f"starvation_age_s must be > 0 (seconds of waiting per "
                f"effective-priority step of starvation aging), got "
                f"{starvation_age_s}")
        if self.megastep_auto and spec_k:
            raise ValueError(
                "megastep='auto' tunes the fused-decode launch from its "
                "own dispatch/step timings; speculative decoding replaces "
                "those launches with draft-and-verify, so there is "
                "nothing to tune — pick an explicit megastep with spec_k")
        self.engine = engine
        self.megastep = int(megastep)
        self.async_decode = bool(async_decode)
        self.async_depth = int(async_depth)
        self.spec_k = int(spec_k) if spec_k is not None else 0
        self.spec_ngram = int(spec_ngram)
        self.prefill_budget = int(prefill_budget)
        self.prefix_cache = bool(prefix_cache)
        self.num_slots = engine.bucket_rows(max(1, num_slots))
        self.max_total_len = int(max_total_len or cfg.n_positions)
        self.max_queue_size = max_queue_size
        self.eos_token = eos_token
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        # Requests that submit without their own SamplingParams inherit
        # the scheduler-wide scalars as a per-request config — ONE code
        # path: every launch builds per-slot vectors, uniform or not.
        self.default_sampling = sampling_lib.SamplingParams(
            temperature=self.temperature, top_k=max(0, self.top_k))
        self.cache_mode = cache_mode
        self.block_size = int(block_size)
        shards = 1
        if cache_mode == "paged":
            if per_shard_kv:
                shards = max(1, engine.data_parallelism)
            # spec_k tail slack: the verify program's width is fixed at
            # k+1, so its pad columns write up to spec_k positions past
            # a full slot's last real index.  Widening the table keeps
            # those positions on trash-pointing entries instead of
            # letting the lookup clamp onto the slot's last real block.
            per_slot = -(-(self.max_total_len + self.spec_k)
                         // self.block_size)
            if num_blocks is None:
                # Safe default: full capacity (every slot at max length)
                # plus the trash block(s) — no savings until sized down,
                # but never any block-wait either.
                num_blocks = self.num_slots * per_slot + shards
            else:
                # Per-shard pools partition the id space evenly; round a
                # hand-picked pool UP to the next multiple of the shard
                # count rather than rejecting it.
                num_blocks = -(-int(num_blocks) // shards) * shards
            # A family with window layers (``cache_geometry`` says how
            # many positions they read) keeps those layers' K/V in a second
            # pool where a slot owns a ring, whatever its length: the
            # window, the longest call's positions (a prefill chunk must
            # still see the window before its first) and a megastep, in
            # whole blocks and one more; never more than the row itself.
            geometry = engine.workload.cache_geometry
            self._kv_geometry = (geometry(PagedKVConfig(
                block_size=self.block_size, num_blocks=int(num_blocks)))
                if geometry is not None else {})
            self._window = int(self._kv_geometry.get("window_positions", 0))
            # A family whose attention reads a learned selection says how
            # many of a row's positions a layer reads at the most.
            self._selected = int(
                self._kv_geometry.get("selected_positions", 0))
            # A family with recurrent layers says what a slot's state
            # costs, whatever the row's length.
            self._state_bytes = int(
                self._kv_geometry.get("state_bytes_per_slot", 0))
            ring = 0
            if self._window:
                chunk = self.prefill_budget or self.max_total_len
                ring = min(per_slot, -(-(self._window + chunk + self.megastep)
                                       // self.block_size) + 1)
            self.paged: Optional[PagedKVConfig] = PagedKVConfig(
                block_size=self.block_size, num_blocks=int(num_blocks),
                kv_dtype=kv_dtype, data_shards=shards,
                window_blocks=self.num_slots * ring + 1 if ring else 0,
                window_ring=ring)
            with default_tracer().span("cache_init", cat="startup"):
                self._cache = engine.init_paged_cache(
                    self.num_slots, self.max_total_len, paged=self.paged)
            self._allocator: Optional[BlockAllocator] = BlockAllocator(
                self.paged.num_blocks, self.block_size, num_shards=shards)
            # Slot -> data shard: contiguous ranges, matching how
            # ``batch_sharding`` partitions the (num_slots, 1) decode rows
            # over the data axes — slot s's rows and its blocks live on
            # the same devices.
            self._slot_shard = [s * shards // self.num_slots
                                for s in range(self.num_slots)]
            # Host-owned logical->physical map, one row per slot; rows
            # (and entries past a slot's allocation) point at the slot's
            # shard's trash block (block 0 in single-shard mode).  Passed
            # into every prefill/decode call.
            # With a window pool the slot's ring entries follow the full
            # layers' in the same row (``PagedKVConfig.split_tables``);
            # slot s's e-th ring entry is always window block 1 + s * ring
            # + e, mapped when the row first reaches it and pointed back
            # at the window pool's trash block (0) at retirement.
            self._full_cols = per_slot
            self._block_tables = np.zeros(
                (self.num_slots, per_slot + ring), np.int32)
            for s in range(self.num_slots):
                self._block_tables[s, :per_slot] = (
                    self._allocator.trash_block(self._slot_shard[s]))
            # Blocks of positions each slot's row has covered so far (the
            # ring holds the last ``ring`` of them).  Loop thread.
            self._window_covered = [0] * self.num_slots
            self._slot_blocks: Dict[int, List[int]] = {
                s: [] for s in range(self.num_slots)}
        else:
            self.paged = None
            self._window = 0
            self._selected = 0
            self._state_bytes = 0
            self._allocator = None
            self._block_tables = None
            self._slot_blocks = {}
            self._slot_shard = [0] * self.num_slots
            # spec_k tail slack, same reason as the paged table above:
            # without it the vmapped ``dynamic_update_slice`` CLAMPS a
            # near-the-end k+1-wide verify write backward, silently
            # overwriting the last real K/V rows (caught as an
            # end-of-stream parity break when max_total_len is sized
            # exactly to prompt + max_new_tokens).
            with default_tracer().span("cache_init", cat="startup"):
                self._cache = engine.init_slot_cache(
                    self.num_slots, self.max_total_len + self.spec_k)
        # Per-slot emitted-token counts (presence/frequency penalties):
        # resident device state beside the KV cache, donated through every
        # slot launch and rebound from its return — same chaining idiom
        # as the cache itself.  Loop-thread state after the ctor.
        self._counts = engine.init_slot_counts(self.num_slots)
        self.kv_hbm_bytes = int(engine.cache_hbm_bytes(self._cache))
        self.kv_hbm_bytes_per_shard = int(
            engine.cache_hbm_bytes_per_shard(self._cache))
        # SLO scheduling: priority/deadline-ranked admission plus
        # preempt/swap/resume under block pressure.  Off (default) the
        # admission loop is the classic head-of-line FIFO, bit-for-bit.
        self.slo_scheduling = bool(slo_scheduling)
        self.swap_min_tokens = int(swap_min_tokens)
        self.starvation_age_s = float(starvation_age_s)
        # Preempted requests parked between eviction and re-admission
        # (loop-thread mutation, read under _lock by stats/cancel/drain).
        self._preempted: List[_SlotRequest] = []
        # Host-RAM KV tier: parks victims' private block bytes.  Paged
        # mode only — dense slo scheduling still ranks admission but has
        # no per-block residency to reclaim, so it never preempts.
        # Lifecycle recorder (obs.lifecycle.LifecycleRecorder or None):
        # a host-side tap the hook sites below feed typed events — only
        # values the loop already holds (timestamps, counts, byte
        # sizes), never a device array.  None (default) keeps every
        # path bit-identical to the unrecorded scheduler.
        self._lifecycle = lifecycle
        self._tier_pool: Optional[HostKVPool] = None
        if self.slo_scheduling and cache_mode == "paged":
            self._tier_pool = HostKVPool(
                engine, paged=self.paged,
                policy=SwapPolicy(swap_min_tokens=self.swap_min_tokens),
                lifecycle=lifecycle)
        # paged: reserved-but-unallocated blocks, per shard
        self._reserved = [0] * shards
        self._blocks_per_request: collections.deque = collections.deque(
            maxlen=1024)
        self._blocks_hist: collections.Counter = collections.Counter()
        self._free: List[int] = list(range(self.num_slots))
        self._active: Dict[int, _SlotRequest] = {}
        self._last_tok = np.zeros((self.num_slots, 1), np.int32)
        # Device-resident decode inputs (loop-thread state): the previous
        # launch's on-device token vector, chained into the next launch
        # with zero host work, and the replicated device copy of the
        # block tables.  Either is None when the host copy is newer —
        # _last_tok after a prefill's host write, _block_tables after any
        # table mutation (allocation growth, prefix map, retire reset).
        self._dev_last_tok = None
        self._dev_block_tables = None
        # Async double-buffering (loop-thread state): slots whose host
        # copy of the last token is newer than the device carry (a
        # prefill wrote it while a launch was in flight) — the next
        # dispatch merges these rows from ``_last_tok`` ON DEVICE via the
        # engine's fresh-row mask instead of round-tripping the carry.
        self._fresh = np.zeros((self.num_slots,), bool)
        # The in-flight launch ring (async mode): dispatched-but-not-
        # resolved records, oldest first, resolved strictly in launch
        # order.  At most ``async_depth`` records sit in the ring right
        # after a dispatch; the resolve loop then drains it back below
        # the depth, so ``async_depth - 1`` unresolved launches persist
        # across iterations (depth 2 = the classic double buffer).
        # Loop-thread state; records are handed to the fetch thread by
        # reference (their Futures are the only cross-thread channel).
        self._ring: "collections.deque[Any]" = collections.deque()
        # Dedicated fetch thread: performs the ``jax.device_get`` half
        # off the loop thread so fetch latency overlaps the NEXT
        # iteration's host scheduling.  Started lazily at the first
        # async dispatch; a None sentinel shuts it down in close().
        self._fetch_q: "queue.Queue[Any]" = queue.Queue()
        self._fetch_thread: Optional[threading.Thread] = None
        # Ring telemetry (under _lock): sync fallbacks taken while
        # async_decode was on, ring occupancy per dispatch, and loop-
        # thread seconds spent blocked on a fetch-thread result (the
        # residual fetch latency the overlap did NOT hide).
        self._async_fallbacks = 0
        self._ring_depth_hist: collections.Counter = collections.Counter()
        self._fetch_wait_s = 0.0
        # On-device iteration clock: cumulative inner decode steps, one
        # int32 carried launch to launch so K>1 TPOT stamps are anchored
        # to real device progress.  ``_device_clock`` is the host mirror,
        # updated at each fetch.
        self._dev_clock = None
        self._device_clock = 0
        # Device-idle accounting: [last-fetch-done .. next-dispatch] gaps
        # where NO launch was in flight (the device sat idle while the
        # host scheduled).  ``_launch_seq`` pairs each fetch with the
        # launch count at its dispatch so an async fetch that already has
        # a successor in flight contributes no gap.
        self._launch_seq = 0
        self._idle_gap_s = 0.0
        self._await_gap_from: Optional[float] = None
        self._window_start: Optional[float] = None
        self._window_end: Optional[float] = None
        # Megastep autotune (``megastep='auto'``): recent host dispatch
        # durations vs realized per-inner-step device times; evaluated on
        # a slow control loop, frozen at the first confident pick.
        self._dispatch_s: collections.deque = collections.deque(maxlen=64)
        self._step_s: collections.deque = collections.deque(maxlen=64)
        self._autotune_frozen = not self.megastep_auto
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: "collections.deque[_SlotRequest]" = collections.deque()
        self._stopped = False
        self._draining = False
        # Hot reload: the generation new admissions pin, and the staged
        # next generation the loop swaps in at its next iteration top.
        # The initial generation aliases the engine's own params (no extra
        # device memory) and tags the restored checkpoint step (0 fresh).
        self._gen = _ParamGeneration(
            params=engine.params,
            generation=int(engine.restored_step or 0))
        self._pending_gen: Optional[_ParamGeneration] = None
        # counters (under _lock)
        self._submitted = 0
        self._rejected = 0
        self._completed = 0
        self._failed = 0
        self._cancelled = 0
        self._admitted = 0
        self._retired = 0
        # SLO scheduling (under _lock): preempt/resume traffic and the
        # TTFT-deadline goodput tallies.
        self._preemptions = 0
        self._preempt_swapped = 0
        self._preempt_recompute = 0
        self._resumes = 0
        self._resumes_swapped = 0
        self._deadline_met = 0
        self._deadline_missed = 0
        # Prefix caching (under _lock): cacheable-block hit/miss totals
        # and prompt tokens whose prefill compute cache hits skipped.
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._prefix_tokens_skipped = 0
        # Chunked prefill (under _lock): chunks launched, slots still
        # mid-prefill, and the un-prefilled prompt-token backlog.
        self._prefill_chunks = 0
        self._prefilling = 0
        self._prefill_backlog = 0
        # Megastep (under _lock): decode launches issued and tokens
        # fetched from them — tokens/launches is the realized
        # amortization, ~K * live generations when slots stay busy.
        self._megastep_launches = 0
        self._megastep_tokens = 0
        # Megastep early exit: inner steps the while_loop actually ran
        # (vs launches * K had every megastep ridden out its full span).
        self._megastep_effective_steps = 0
        # Expert layers that count the router's choices on the device
        # (under _lock): the decode launches' ``moe_counts`` rows summed,
        # (expert layers, experts held + 3); None until a launch brings one.
        self._moe_counts: Optional[np.ndarray] = None
        # The engine's record of the form each traced program's expert
        # layers took: a launch that traces its program writes it
        # (``grouped_matmul.record_forms`` round the launch).
        self._moe_forms = engine.expert_forms_record()
        # Speculative decoding (under _lock): verify launches, draft
        # tokens proposed / accepted, and tokens emitted by the verify
        # path (accepted drafts + the per-slot bonus/correction token).
        self._spec_launches = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._spec_emitted = 0
        self._iterations = 0
        self._decode_counter = 0  # fold_in counter for the in-step RNG
        self._occupancy_sum = 0
        # Cached positions (prompt + generated) the rows of each plain
        # decode launch held at its dispatch, summed: what the launch's
        # attention has to read at the least.
        self._live_positions_sum = 0
        # The same, each row counted up to the window (what a window
        # layer's attention reads), and ring entries written over.
        self._live_window_positions_sum = 0
        self._window_recycled = 0
        # The same, each row counted up to the selection (the latent rows a
        # layer's attention reads where a learned indexer selects them).
        self._live_selected_positions_sum = 0
        # Admissions that started a slot's recurrent state from zero.
        self._state_resets = 0
        self._last_occupancy = 0
        self._latencies_ms: collections.deque = collections.deque(maxlen=1024)
        self._ttft_ms: collections.deque = collections.deque(maxlen=1024)
        self._ttfb_ms: collections.deque = collections.deque(maxlen=1024)
        self._tpot_ms: collections.deque = collections.deque(maxlen=1024)
        # Individual inter-token gaps (every decoded token's wait, across
        # all requests) — the distribution whose tail chunked prefill
        # bounds: unchunked, a whale prompt's whole prefill lands inside
        # ONE unlucky gap; chunked, no gap carries more than a budget's
        # worth of prefill.  tpot_p50/p99 come from here; tpot_mean stays
        # the per-request mean (decode cadence per stream).
        self._tpot_gaps_ms: collections.deque = collections.deque(
            maxlen=4096)
        self._queue_wait_ms: collections.deque = collections.deque(maxlen=1024)
        self._obs = _continuous_instruments()
        self._obs_registry = obs_metrics.default_registry()
        self.obs_namespace = self._obs_registry.register_stats(
            f"serve/{name}", self.stats
        )
        self._tracer = default_tracer()
        # slot -> its open turnover (retirement with a request waiting ->
        # first decode launch of the successor).  Loop thread only.
        self._turnover: Dict[int, _Turnover] = {}
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name=name)
        if start:
            self._thread.start()

    # -- client surface ------------------------------------------------------

    def submit(self, prompt: np.ndarray, *,
               max_new_tokens: int = 16,
               eos_token: Optional[int] = None,
               sampling=None,
               on_token=None) -> Future:
        """Enqueue one prompt; Future resolves to its 1-D token array the
        moment ITS slot retires (out of submission order by design).

        ``on_token`` streams the request: the LOOP thread calls it with
        each batch of newly fetched tokens (a list of ints — one per
        iteration at K=1, up to K per megastep, post-trim on the
        spec/async paths) the moment they land on host.  The callback
        must be cheap and non-blocking (hand off to a queue — see
        ``serve.gateway.TokenStream``); it must NOT call back into the
        scheduler.  A callback that raises is disabled for the rest of
        the stream (the request itself still completes).  The Future
        resolves to the SAME full token array either way — streaming is
        delivery, not a different decode.

        ``sampling`` is the request's own config — a
        ``serve.sampling.SamplingParams`` or a kwargs dict for one
        (temperature / top_k / top_p / presence_penalty /
        frequency_penalty / seed); ``None`` inherits the scheduler-wide
        scalars.  Mixing configs across slots never recompiles: the
        values ride into ONE compiled program per family as per-slot
        runtime vectors.  Validation (and TypeError for a bad shape)
        happens HERE on the submitting thread.

        Rejection happens HERE, not mid-decode: a request that can never
        fit its slot (``prompt_len + max_new_tokens > max_total_len``, an
        empty prompt, or — paged mode — a worst-case block footprint the
        whole pool cannot hold) fails with ``ValueError`` at submit time
        instead of being admitted and dying halfway through its stream.

        Raises ``ServeOverloadedError`` when the admission queue is at
        ``max_queue_size`` and ``RuntimeError`` after ``close()``.
        """
        if on_token is not None and not callable(on_token):
            raise TypeError(
                f"on_token must be callable (called with each list of "
                f"newly decoded tokens), got {type(on_token).__name__}")
        sampling = (self.default_sampling if sampling is None
                    else sampling_lib.coerce(sampling))
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) < 1:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) + max_new_tokens > self.max_total_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new_tokens {max_new_tokens} "
                f"exceeds max_total_len {self.max_total_len}; the request "
                f"would be admitted and then fail mid-decode — rejected at "
                f"submit instead")
        if self.paged is not None:
            need = self.paged.blocks_for(len(prompt) + max_new_tokens - 1)
            # Per-shard pools: a request's whole footprint must fit the
            # ONE shard its slot will be pinned to — peers cannot lend.
            if need > self._allocator.capacity_per_shard:
                raise ValueError(
                    f"request needs up to {need} KV blocks (prompt "
                    f"{len(prompt)} + max_new_tokens {max_new_tokens}, "
                    f"block_size {self.block_size}) but the pool only has "
                    f"{self._allocator.capacity_per_shard} usable blocks "
                    f"per shard — it could never be admitted")
        req = _SlotRequest(
            prompt=prompt, max_new_tokens=max_new_tokens,
            eos_token=self.eos_token if eos_token is None else eos_token,
            future=Future(), submitted=_now(),
            sampling=sampling, on_token=on_token)
        if self.prefix_cache:
            # Hash the prompt's full blocks HERE on the client thread —
            # pure compute, so the loop thread only ever walks the map.
            req.prefix_keys = chain_block_keys(prompt, self.block_size)
        with self._cond:
            if self._stopped:
                raise RuntimeError("ContinuousScheduler is closed")
            if self._draining:
                self._rejected += 1
                self._obs["rejected"].inc()
                raise ServeOverloadedError(
                    "scheduler is draining — not admitting new requests")
            if len(self._queue) >= self.max_queue_size:
                self._rejected += 1
                self._obs["rejected"].inc()
                raise ServeOverloadedError(
                    f"admission queue full ({len(self._queue)}/"
                    f"{self.max_queue_size} queued); back off and retry")
            self._queue.append(req)
            self._submitted += 1
            req.rid = self._submitted
            # The router stitches its route span into this request's
            # trace lane through the Future.
            req.future.rid = req.rid
            self._obs["submitted"].inc()
            self._obs["depth"].set(len(self._queue))
            self._cond.notify()
            depth = len(self._queue)
        if self._lifecycle is not None:
            # Host-side tap, outside the scheduler lock: the submit
            # stamp the request already carries, plus the depth it
            # queued behind.  QUEUED is export-only colour (the fold
            # keys queue_wait off SUBMIT -> ADMITTED alone).
            self._lifecycle.record(
                req.rid, "SUBMIT", t=req.submitted,
                prompt_len=int(len(prompt)),
                max_new_tokens=int(max_new_tokens))
            if self._lifecycle.verbose_loop_events:
                self._lifecycle.record(req.rid, "QUEUED", depth=depth)
        return req.future

    def submit_payload(self, payload: Any) -> Future:
        """``DynamicBatcher(iteration_level=True)`` adapter: a raw array is
        a prompt; a dict carries ``prompt`` plus per-request options
        (``max_new_tokens``, ``eos_token``, ``sampling`` — a
        ``SamplingParams`` or kwargs dict); a (prompt, max_new_tokens)
        tuple is the driver's mixed-traffic shape."""
        if isinstance(payload, dict):
            return self.submit(payload["prompt"], **{
                k: v for k, v in payload.items() if k != "prompt"})
        if isinstance(payload, tuple) and len(payload) == 2:
            return self.submit(payload[0], max_new_tokens=int(payload[1]))
        return self.submit(payload)

    def cancel(self, rid: int) -> bool:
        """Cancel one request by its ``rid`` (stamped on the Future at
        submit).  Returns True when the request was found live.

        A QUEUED request is removed before admission and its Future
        cancelled here, synchronously — it never touches a slot.  An
        ACTIVE request is flagged under the lock and retired by the loop
        at its next iteration boundary: the slot frees, its KV blocks
        and reservation release (refcounted prefix shares decrement),
        and the Future resolves cancelled — ``result()`` raises
        ``CancelledError``.  Tokens already fetched stay on the Future's
        request record but nothing further streams: ``on_token``
        delivery stops the moment the flag is set.  False means the rid
        is unknown or the request already retired (its Future already
        carries the full result — cancellation lost the race, which the
        caller can observe via ``future.done()``)."""
        queued: Optional[_SlotRequest] = None
        parked = False
        with self._cond:
            for i, r in enumerate(self._queue):
                if r.rid == rid:
                    queued = r
                    del self._queue[i]
                    break
            if queued is None:
                # Preempted-and-parked requests hold no slot: cancel
                # them here like queued ones (their parked host KV, if
                # any, is dropped below, outside the lock).
                for i, r in enumerate(self._preempted):
                    if r.rid == rid:
                        queued = r
                        parked = True
                        del self._preempted[i]
                        break
            if queued is not None:
                self._cancelled += 1
                self._obs["cancelled"].inc()
                self._obs["depth"].set(len(self._queue))
            else:
                for r in self._active.values():
                    if (r.rid == rid and not r.cancelled
                            and r.finished_at is None):
                        r.cancelled = True
                        # Wake the loop: the sweep at the next iteration
                        # top retires the slot (flushing any in-flight
                        # async launch first so freed blocks can't take
                        # a zombie device write).
                        self._cond.notify_all()
                        return True
                return False
        # Outside the lock: Future callbacks (gateway stream finishers)
        # run inline on this thread.  The tier pool serializes ledger
        # access internally, so the parked payload drop needs no
        # scheduler lock.
        if parked and self._tier_pool is not None:
            self._tier_pool.drop(rid)
        if self._lifecycle is not None:
            self._lifecycle.record(rid, "CANCELLED", parked=parked)
        queued.future.cancel()
        return True

    # -- hot weight reload ----------------------------------------------------

    def update_params(self, params: Any, *, generation: int) -> None:
        """Stage a new weight generation (fleet checkpoint watcher).

        ``params`` must already be device-sharded through the engine's
        rules (``ServeEngine.shard_params``) with the same avals as the
        serving params — the slot programs take params as their
        non-donated first argument, so the swap never recompiles.  The
        loop installs the staged generation at the top of its next
        iteration: requests already admitted keep decoding on the
        generation they pinned; every admission after the swap pins the
        new one.  Back-to-back updates before the loop wakes coalesce —
        only the newest staged generation is ever installed.
        """
        staged = _ParamGeneration(params=params, generation=int(generation))
        with self._cond:
            if self._stopped:
                raise RuntimeError("ContinuousScheduler is closed")
            self._pending_gen = staged
            self._cond.notify_all()

    @property
    def generation(self) -> int:
        """The checkpoint-step tag new admissions currently pin."""
        with self._lock:
            return self._gen.generation

    # -- graceful drain -------------------------------------------------------

    def drain(self, timeout: float = 30.0) -> bool:
        """Graceful-shutdown phase 1: stop admitting (``submit`` sheds
        with ``ServeOverloadedError``), fail the queued-but-unadmitted
        backlog the same way, and wait up to ``timeout`` seconds for every
        RESIDENT slot to finish its stream.  Returns True when all active
        slots retired in time.  Call ``close()`` afterwards; idempotent
        and safe to call on an already-stopped scheduler."""
        deadline = _now() + float(timeout)
        with self._cond:
            self._draining = True
            shed = [r for r in self._queue if not r.future.done()]
            self._queue.clear()
            self._rejected += len(shed)
            if shed:
                self._obs["rejected"].inc(len(shed))
            self._obs["depth"].set(0)
            self._cond.notify_all()
        for req in shed:
            # PENDING -> RUNNING fences out a concurrent client cancel;
            # False means the cancel already resolved this future.
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(ServeOverloadedError(
                    "scheduler draining: request shed before admission"))
        with self._cond:
            # Preempted requests were already admitted once — their
            # Futures are promised, so drain resumes them (the SLO
            # admission pass considers parked requests even while
            # draining) and waits for them too.
            finished = self._cond.wait_for(
                lambda: ((not self._active and not self._preempted)
                         or self._stopped),
                timeout=max(0.0, deadline - _now()))
        return bool(finished)

    @property
    def paged_equivalent_blocks(self) -> int:
        """Blocks a dense slot pins for its whole lifetime: the full
        ``max_total_len`` row, expressed in ``block_size`` units so dense
        and paged block gauges are directly comparable."""
        return -(-self.max_total_len // self.block_size)

    def _block_stats(self) -> Dict[str, float]:
        """Block-pool gauges (call under ``_lock``).  Dense mode reports
        its trivially-full equivalent — every slot permanently pins a full
        row — so dashboards show exactly what paging reclaims."""
        if self._allocator is not None:
            out = self._allocator.stats()
        else:
            total = float(self.num_slots * self.paged_equivalent_blocks)
            out = {
                "blocks_total": total,
                "blocks_free": 0.0,
                "blocks_in_use": total,
                "block_utilization": 1.0,
                "blocks_high_water": total,
            }
        per_req = sorted(self._blocks_per_request)
        out["blocks_per_request_mean"] = (
            sum(per_req) / len(per_req) if per_req else 0.0)
        out["blocks_per_request_p50"] = _percentile(per_req, 0.50)
        out["blocks_per_request_max"] = float(per_req[-1]) if per_req else 0.0
        out["block_size"] = float(self.block_size)
        out["kv_hbm_bytes"] = float(self.kv_hbm_bytes)
        out["kv_hbm_bytes_per_shard"] = float(self.kv_hbm_bytes_per_shard)
        return out

    def blocks_per_request_hist(self) -> Dict[int, int]:
        """Histogram of blocks pinned per retired request (all-time)."""
        with self._lock:
            return dict(self._blocks_hist)

    def stats(self) -> Dict[str, float]:
        """Counter snapshot (ServeMonitorHook export surface).  Includes
        the iteration-level counters: slot occupancy, admissions /
        retirements per iteration, TTFT / TPOT percentiles, and the
        block-pool gauges (trivially full in dense mode)."""
        # Engine program-cache telemetry: reads dict sizes + internally
        # locked obs counters only, and runs BEFORE the scheduler lock so
        # no lock-order edge forms against the launch paths.
        compile_stats = self.engine.compile_stats()
        attention = self.engine.decode_attention_launches()
        attention_launches = sum(attention.values())
        moe_forms = dict(self._moe_forms).items()
        # Host-KV-tier telemetry: the pool has its own lock, read it
        # before the scheduler lock (same no-lock-order-edge discipline
        # as compile_stats).  Zeros when tiering is off so dashboards,
        # the fleet router, and the driver read one uniform key set.
        if self._tier_pool is not None:
            tier_stats = self._tier_pool.stats()
        else:
            tier_stats = {k: 0.0 for k in (
                "swapped_resident", "swapped_bytes_resident",
                "swap_out_bytes_total", "swap_in_bytes_total",
                "swap_bytes_total", "swap_outs_total", "swap_ins_total",
                "swap_dropped_total")}
        # Lifecycle attribution: the recorder has its own lock, read it
        # before the scheduler lock (same discipline as compile_stats /
        # tier_stats).  The zero dict keeps the key set uniform with the
        # recorder off.
        if self._lifecycle is not None:
            lifecycle_stats = self._lifecycle.stats()
        else:
            lifecycle_stats = dict(EMPTY_LIFECYCLE_STATS)
        with self._lock:
            lat = sorted(self._latencies_ms)
            ttft = sorted(self._ttft_ms)
            tpot = self._tpot_ms
            qw = sorted(self._queue_wait_ms)
            iters = self._iterations
            prefix_lookups = self._prefix_hits + self._prefix_misses
            sampling_configs = len({r.sampling
                                    for r in self._active.values()
                                    if r.sampling is not None})
            return {
                **self._block_stats(),
                "queue_depth": float(len(self._queue)),
                "capacity": float(self.max_queue_size),
                "submitted": float(self._submitted),
                "completed": float(self._completed),
                "rejected": float(self._rejected),
                "failed": float(self._failed),
                "cancelled": float(self._cancelled),
                "num_slots": float(self.num_slots),
                "active_slots": float(len(self._active)),
                "admitted": float(self._admitted),
                "retired": float(self._retired),
                "iterations": float(iters),
                "slot_occupancy": (
                    self._occupancy_sum / (iters * self.num_slots)
                    if iters else 0.0),
                "last_occupancy": float(self._last_occupancy),
                # Mean cached positions a decode launch's rows held at
                # dispatch (plain decode launches; per iteration, like
                # slot_occupancy): the least its attention reads.
                "decode_live_positions": (
                    self._live_positions_sum / iters if iters else 0.0),
                **self._two_pool_stats_locked(),
                "admissions_per_iter": (
                    self._admitted / iters if iters else 0.0),
                "retirements_per_iter": (
                    self._retired / iters if iters else 0.0),
                "p50_latency_ms": _percentile(lat, 0.50),
                "p99_latency_ms": _percentile(lat, 0.99),
                "ttft_p50_ms": _percentile(ttft, 0.50),
                "ttft_p99_ms": _percentile(ttft, 0.99),
                # Streaming time-to-first-byte: submit -> first token
                # handed OFF the loop thread (TTFT plus the emit hop) —
                # what a gateway client actually waits for.
                "ttfb_p50_ms": _percentile(sorted(self._ttfb_ms), 0.50),
                "ttfb_p99_ms": _percentile(sorted(self._ttfb_ms), 0.99),
                "tpot_mean_ms": (sum(tpot) / len(tpot)) if tpot else 0.0,
                "queue_wait_p50_ms": _percentile(qw, 0.50),
                "queue_wait_p99_ms": _percentile(qw, 0.99),
                "param_generation": float(self._gen.generation),
                "prefix_hits": float(self._prefix_hits),
                "prefix_misses": float(self._prefix_misses),
                "prefix_hit_rate": (self._prefix_hits / prefix_lookups
                                    if prefix_lookups else 0.0),
                "prefill_tokens_skipped": float(
                    self._prefix_tokens_skipped),
                # Gap-based TPOT percentiles (one sample per decoded
                # token): the tail chunked prefill bounds — unlike
                # tpot_mean_ms, whose per-request averaging washes a
                # single whale stall out over the whole stream.
                "tpot_p50_ms": _percentile(
                    sorted(self._tpot_gaps_ms), 0.50),
                "tpot_p99_ms": _percentile(
                    sorted(self._tpot_gaps_ms), 0.99),
                "prefill_budget": float(self.prefill_budget),
                "prefilling_slots": float(self._prefilling),
                "prefill_backlog_tokens": float(self._prefill_backlog),
                "prefill_chunks": float(self._prefill_chunks),
                "megastep": float(self.megastep),
                "megastep_auto": 1.0 if self.megastep_auto else 0.0,
                "megastep_autotune_frozen": (
                    1.0 if self._autotune_frozen else 0.0),
                "megastep_launches": float(self._megastep_launches),
                "megastep_tokens": float(self._megastep_tokens),
                "megastep_effective_steps": float(
                    self._megastep_effective_steps),
                # Async double buffering: whether the loop dispatches
                # before fetching, the device-side cumulative inner-step
                # clock (host mirror, advanced at each fetch), and the
                # fraction of the decode window the device sat with no
                # launch in flight — the overlap headline (async on must
                # shrink it toward zero).
                "async_decode": 1.0 if self.async_decode else 0.0,
                "device_clock": float(self._device_clock),
                "device_idle_fraction": self._idle_fraction_locked(),
                # The launch ring: configured depth, async iterations
                # that ran at depth 1 (spec/prefill compose now, so
                # steady-state async traffic should hold this at zero),
                # realized ring occupancy at dispatch, and loop-thread
                # seconds spent blocked on the fetch thread (residual
                # fetch latency the overlap did NOT hide).
                "async_depth": float(self.async_depth),
                "async_sync_fallbacks": float(self._async_fallbacks),
                "async_ring_depth_avg": (
                    sum(d * c for d, c in self._ring_depth_hist.items())
                    / sum(self._ring_depth_hist.values())
                    if self._ring_depth_hist else 0.0),
                "async_ring_depth_max": float(
                    max(self._ring_depth_hist)
                    if self._ring_depth_hist else 0),
                "async_fetch_wait_s": float(self._fetch_wait_s),
                "spec_k": float(self.spec_k),
                "spec_launches": float(self._spec_launches),
                "spec_drafted": float(self._spec_drafted),
                "spec_accepted": float(self._spec_accepted),
                "spec_emitted": float(self._spec_emitted),
                "spec_acceptance_rate": (
                    self._spec_accepted / self._spec_drafted
                    if self._spec_drafted else 0.0),
                "spec_tokens_per_launch": (
                    self._spec_emitted / self._spec_launches
                    if self._spec_launches else 0.0),
                # Per-request sampling: distinct configs resident right
                # now vs the ONE compiled program set serving them all —
                # the flat-program-count claim, numerically.
                "sampling_configs_active": float(sampling_configs),
                "programs_cached": compile_stats["programs_cached"],
                "compile_total": compile_stats["compile_total"],
                # How often the block-table attention kernel engages: the
                # share of paged decode launches whose program was traced
                # with it (0 with no such launch yet, and on the CPU).
                "decode_attention_kernel_share": (
                    sum(attention[path] for path in KERNEL_PATHS)
                    / attention_launches if attention_launches else 0.0),
                **self._moe_stats_locked(),
                # What each traced program's expert layers took: every
                # assignment once over rows grouped by expert, or every
                # held expert over every token; and the grouped buffer's
                # static rows.  Empty for a model without expert layers.
                "moe_expert_form": {
                    program: form for program, (form, _) in moe_forms},
                "moe_grouped_rows": {
                    program: rows for program, (_, rows) in moe_forms},
                # SLO scheduling: preempt/resume traffic, parked
                # requests, host-KV-tier bytes, and TTFT-deadline
                # goodput (fraction of deadline-carrying completions
                # whose first token met its deadline_ms).
                "slo_scheduling": 1.0 if self.slo_scheduling else 0.0,
                "preemptions_total": float(self._preemptions),
                "preempt_swapped_total": float(self._preempt_swapped),
                "preempt_recompute_total": float(
                    self._preempt_recompute),
                "resumes_total": float(self._resumes),
                "resume_swapped_total": float(self._resumes_swapped),
                "preempted_pending": float(len(self._preempted)),
                "deadline_met_total": float(self._deadline_met),
                "deadline_missed_total": float(self._deadline_missed),
                "deadline_goodput": (
                    self._deadline_met
                    / (self._deadline_met + self._deadline_missed)
                    if (self._deadline_met + self._deadline_missed)
                    else 0.0),
                **tier_stats,
                **lifecycle_stats,
            }

    def close(self, timeout: float = 30.0) -> None:
        """Stop the loop; fail queued and in-flight futures.  Idempotent.
        The iteration in progress finishes first — its retirements resolve
        normally."""
        with self._cond:
            if self._stopped:
                return
            self._stopped = True
            self._cond.notify_all()
        if self.obs_namespace:
            self._obs_registry.unregister_stats(self.obs_namespace)
        if self._thread.is_alive():
            self._thread.join(timeout)
        if self._fetch_thread is not None:
            # The loop's exit path drained the ring, so every queued
            # record has been resolved; the sentinel wakes the worker
            # to exit.  (Loop-death leftovers resolve into Futures no
            # one reads — harmless — before the sentinel is reached.)
            self._fetch_q.put(None)
            self._fetch_thread.join(timeout)
        with self._cond:
            leftover = (list(self._queue) + list(self._active.values())
                        + list(self._preempted))
            self._queue.clear()
            self._active.clear()
            self._preempted.clear()
            self._free = list(range(self.num_slots))
        for req in leftover:
            if (not req.future.done()
                    and req.future.set_running_or_notify_cancel()):
                req.future.set_exception(
                    RuntimeError("ContinuousScheduler closed"))

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False

    # -- the persistent decode loop ------------------------------------------

    def _loop(self) -> None:
        try:
            while True:
                if self._iteration():
                    return
        except BaseException as e:  # noqa: BLE001 — forwarded to futures
            logger.exception("continuous scheduler loop died")
            with self._cond:
                self._stopped = True
                doomed = (list(self._queue) + list(self._active.values())
                          + list(self._preempted))
                self._queue.clear()
                self._active.clear()
                self._preempted.clear()
                self._failed += len(doomed)
                self._obs["failed"].inc(len(doomed))
            for req in doomed:
                if (not req.future.done()
                        and req.future.set_running_or_notify_cancel()):
                    req.future.set_exception(e)

    def _iteration(self) -> bool:
        """One scheduler iteration; True means the loop should exit.

        The host-scheduling half (generation install, admission walk,
        chunked prefill) runs BEFORE the decode call — with async decode
        on, that host work overlaps the previous iteration's in-flight
        device launch instead of alternating with it."""
        with self._tracer.span("iteration", cat="serve"):
            return self._iteration_body()

    def _iteration_body(self) -> bool:
        with self._cond:
            while (not self._stopped and not self._active
                   and not self._queue
                   and not self._preempted
                   and self._pending_gen is None
                   and not self._ring):
                # Parked for want of work: no queue, no active slot,
                # nothing in flight.  Device idle time under this span is
                # the traffic's, not the scheduler's.
                with self._tracer.span("idle_wait", cat="serve"):
                    self._cond.wait()
            stopped = self._stopped
            cancels = ([] if stopped else
                       [r for r in self._active.values() if r.cancelled])
        if stopped:
            # close() while a launch was in flight: resolve it so its
            # requests' already-computed tokens retire normally instead
            # of failing.  Outside the cond block — the fetch takes
            # self._lock, which is not reentrant.
            self._flush_inflight()
            return True
        if cancels:
            # Cancel sweep, BEFORE admission so the freed slots (and
            # their blocks/reservations) are admittable this same
            # iteration.  A dispatched-but-unfetched async launch may
            # still be writing a cancelled slot's blocks, so resolve it
            # first — freed blocks must never take a zombie device
            # write.  The flush itself retires rows that hit their eos
            # in flight; ``finished_at`` guards the double retire.
            self._flush_inflight()
            for req in cancels:
                if req.finished_at is None:
                    self._retire(req)
        with self._tracer.span("host_sched", cat="serve") as host_span:
            admitted, refill = self._host_sched()
            host_span.set(admitted=admitted, inflight=len(self._ring))
        if refill:
            # Megastep admission alignment: a K-step launch pins
            # its rows for K iterations, so a request that missed
            # this boundary by milliseconds would decode phase-
            # shifted from its wave forever, wasting masked
            # slot-steps at every retirement.  When this iteration
            # admitted something and (as of the locked admission
            # pass above) the queue and free slots were both
            # non-empty, keep admitting and prefilling, THEN
            # launch the fused step — rows admitted together
            # advance and retire together.  Never taken when this
            # iteration admitted nothing (a blocked head of line
            # must not starve decode), and a no-op for K=1, whose
            # admission granularity is already one step.
            return False
        self._decode_once()
        if self.megastep_auto:
            with self._lock:
                due = (not self._autotune_frozen
                       and self._iterations % _AUTOTUNE_EVERY == 0)
            if due:
                self._autotune_eval()
        return False

    def _host_sched(self) -> Tuple[int, bool]:
        """The host-scheduling half of an iteration: generation install,
        the admission walk, ``_admit`` and ``_prefill_step`` (whose
        launches are its ``prefill_chunk`` children).  Returns how many
        requests it admitted and whether the megastep admission alignment
        asks for another round before the decode launch."""
        admits: List[_SlotRequest] = []
        gen_swapped = False
        with self._cond:
            if self._pending_gen is not None:
                # Install the staged weight generation: every
                # admission from here on pins it; rows already
                # active keep their own generation's params.
                old, self._gen = self._gen, self._pending_gen
                self._pending_gen = None
                gen_swapped = True
                if old.refs == 0:
                    old.params = None  # nothing in flight holds it
                logger.info(
                    "hot-swapped params: generation %d -> %d "
                    "(%d request(s) still on the old weights)",
                    old.generation, self._gen.generation, old.refs)
            if not self.slo_scheduling:
                while (self._queue and self._free
                       and not self._draining):
                    idx = self._pick_slot_locked(self._queue[0])
                    if idx is None:
                        break  # head of line waits on KV blocks
                    req = self._queue.popleft()
                    req.slot = self._free.pop(idx)
                    if self.paged is not None:
                        # Reserve the worst-case block count now so a
                        # mid-decode boundary cross can always be
                        # served — admission is what waits on blocks,
                        # never a half-decoded stream.
                        req.reserved_blocks = self.paged.blocks_for(
                            req.max_written_tokens())
                        self._reserved[self._slot_shard[req.slot]] += (
                            req.reserved_blocks)
                    req.gen = self._gen
                    self._gen.refs += 1
                    admits.append(req)
                if (self.paged is not None and self._queue
                        and self._free
                        and self._queue[0].blocked_since is None):
                    # Head of line is waiting on BLOCKS, not slots:
                    # start its reservation-wait span.
                    self._queue[0].blocked_since = _now()
            self._obs["depth"].set(len(self._queue))
            refill = (self.megastep > 1 and bool(admits)
                      and bool(self._queue) and bool(self._free)
                      and not self._draining)
        if gen_swapped and self.prefix_cache:
            # Cached K/V is a function of the weights that wrote
            # it: a new generation drops every key (before this
            # iteration's admissions, which pin the new params).
            # In-flight shares keep their refcounts and free
            # normally at retirement.
            dropped = self._allocator.invalidate_prefix_cache()
            if dropped:
                logger.info(
                    "hot reload invalidated %d prefix-cached "
                    "block(s)", dropped)
        if gen_swapped and self._tier_pool is not None:
            # Parked private KV is a function of the weights that wrote
            # it: a generation swap invalidates every swapped payload —
            # those requests resume via the recompute path on the NEW
            # generation.
            with self._lock:
                parked = list(self._preempted)
            invalidated = 0
            for r in parked:
                if self._tier_pool.drop(r.rid):
                    self._requeue_recompute(r)
                    invalidated += 1
            if invalidated:
                logger.info(
                    "hot reload invalidated %d swapped KV payload(s) "
                    "-> recompute on resume", invalidated)
        if self.slo_scheduling:
            slo_admits, resumed = self._slo_admit()
            admits += slo_admits
            if admits or resumed:
                with self._lock:
                    # Same megastep admission-alignment refill as the
                    # FIFO branch computed under its own lock hold
                    # (megastep read included — autotune retunes it
                    # from the loop thread under this lock).
                    refill = (self.megastep > 1 and bool(self._queue)
                              and bool(self._free)
                              and not self._draining)
        self._admit(admits)
        self._prefill_step()
        return len(admits), refill

    def _pick_slot_locked(self, req: _SlotRequest) -> Optional[int]:
        """Index into ``self._free`` of the slot to admit ``req`` into, or
        None when no shard can cover its worst-case block footprint (the
        head of line then waits — no skipping, so admission stays FIFO).

        Paged admission also waits on blocks: the slot's shard must cover
        the request's footprint BEYOND what is already promised to
        in-flight requests there (their unallocated reservations).  With
        several eligible shards the one with the most headroom wins
        (load-levelling the pools); single-shard and dense modes keep the
        classic pop-last (LIFO slot reuse) behaviour exactly."""
        if not self._free:
            return None
        if self.paged is None:
            return len(self._free) - 1
        need = self.paged.blocks_for(req.max_written_tokens())
        best, best_headroom = None, need - 1
        for i in range(len(self._free) - 1, -1, -1):
            sh = self._slot_shard[self._free[i]]
            # Zero-ref prefix-cached blocks count as headroom: allocate()
            # evicts them LRU-first, so caching never steals admission
            # capacity from live requests.
            headroom = (self._allocator.free_count_shard(sh)
                        + self._allocator.evictable_count_shard(sh)
                        - self._reserved[sh])
            if headroom > best_headroom:
                best, best_headroom = i, headroom
        return best

    # -- SLO scheduling: ranked admission + preempt/swap/resume ---------------

    def _eff_priority(self, req: _SlotRequest, now: float) -> int:
        """Effective tier: the request's own priority plus one step per
        ``starvation_age_s`` of waiting since submit (starvation aging —
        background work climbs until nothing outranks it), clamped to
        the top tier."""
        p = req.sampling.priority if req.sampling is not None else 0
        aged = int((now - req.submitted) / self.starvation_age_s)
        return min(sampling_lib.MAX_PRIORITY, p + aged)

    def _rank_key(self, req: _SlotRequest, now: float):
        """Admission rank (ascending = admit first): effective priority
        DESC, then deadline slack ASC (closest TTFT deadline first; no
        deadline sorts last within the tier), then arrival."""
        s = req.sampling
        if s is not None and s.deadline_ms is not None:
            slack = req.submitted + s.deadline_ms / 1000.0 - now
        else:
            slack = float("inf")
        return (-self._eff_priority(req, now), slack, req.submitted,
                req.rid)

    def _pick_victim_locked(self, cand: _SlotRequest,
                            now: float) -> Optional[_SlotRequest]:
        """The active request to preempt so ``cand`` can admit: the
        WORST-ranked resident whose effective priority is STRICTLY below
        the candidate's — equal tiers never preempt each other (so the
        top tier is never preempted: nothing outranks it), and a victim
        aged up to the candidate's tier is protected.  None = nothing
        preemptible; the candidate waits."""
        if self._tier_pool is None:
            return None
        cand_p = self._eff_priority(cand, now)
        victims = [r for r in self._active.values()
                   if not r.cancelled and r.finished_at is None
                   and self._eff_priority(r, now) < cand_p]
        if not victims:
            return None
        return max(victims, key=lambda r: self._rank_key(r, now))

    def _unpark_locked(self, req: _SlotRequest) -> bool:
        """Remove ``req`` from the parked list by IDENTITY (dataclass
        ``==`` compares numpy fields — never use ``in``/``remove``)."""
        for i, r in enumerate(self._preempted):
            if r is req:
                del self._preempted[i]
                return True
        return False

    def _slo_admit(self) -> Tuple[List[_SlotRequest], int]:
        """Priority/deadline-ranked admission over the queue AND the
        parked (preempted) requests, preempting lower-priority residents
        under block pressure.  Returns (requests to ``_admit`` — fresh
        plus recompute resumes, already holding slot + reservation +
        pinned generation) and the count resumed in place by swap
        restore.

        Loop shape: rank all candidates under the lock, try to place the
        best; on block pressure pick a victim and preempt it OUTSIDE the
        lock (the eviction gathers KV through the engine's jitted block
        programs and must flush the in-flight launch first — iteration
        boundary), then retry.  Each preemption removes one resident, so
        the walk terminates.  Parked requests are considered even while
        draining: they were admitted once, their Futures are promised."""
        admits: List[_SlotRequest] = []
        resumed = 0
        while True:
            victim: Optional[_SlotRequest] = None
            claimed: Optional[_SlotRequest] = None
            swap_entry = None
            with self._cond:
                if self._stopped:
                    break
                now = _now()
                cands: List[_SlotRequest] = list(self._preempted)
                if not self._draining:
                    cands.extend(self._queue)
                if not cands or not self._free:
                    break
                cands.sort(key=lambda r: self._rank_key(r, now))
                best = cands[0]
                idx = self._pick_slot_locked(best)
                if idx is None:
                    victim = self._pick_victim_locked(best, now)
                    if victim is None:
                        break  # nothing outrankable resident: wait
                else:
                    from_parked = self._unpark_locked(best)
                    if not from_parked:
                        for i, r in enumerate(self._queue):
                            if r is best:
                                del self._queue[i]
                                break
                    best.slot = self._free.pop(idx)
                    if self.paged is not None:
                        best.reserved_blocks = self.paged.blocks_for(
                            best.max_written_tokens())
                        self._reserved[self._slot_shard[best.slot]] += (
                            best.reserved_blocks)
                    best.gen = self._gen
                    self._gen.refs += 1
                    if from_parked:
                        self._resumes += 1
                        self._obs["resumes"].inc()
                        entry = (self._tier_pool.get(best.rid)
                                 if self._tier_pool is not None else None)
                        if (entry is not None and entry.generation
                                == self._gen.generation):
                            swap_entry = entry
                    claimed = best
                    self._obs["depth"].set(len(self._queue))
            if victim is not None:
                self._flush_inflight()
                self._preempt(victim)
                continue
            if claimed is None:
                break
            if swap_entry is not None:
                if self._resume_swapped(claimed, swap_entry):
                    resumed += 1
                    continue
                # Shared prefix chain evicted while parked:
                # _resume_swapped dropped the payload and reset the
                # request for recompute — fall through to _admit.
            elif (self._tier_pool is not None
                    and self._tier_pool.drop(claimed.rid)):
                # Parked payload from a superseded generation (staged
                # swap raced the proactive invalidation): recompute.
                self._requeue_recompute(claimed)
            admits.append(claimed)
        return admits, resumed

    def _requeue_recompute(self, req: _SlotRequest) -> None:
        """Reset a preempted request for the RECOMPUTE resume path: fold
        the tokens emitted so far into the prompt — a re-prefill of the
        full history writes the identical K/V at the identical positions
        and its final chunk emits the genuinely-next token (the
        written-positions invariant: after n emitted tokens the cache
        held base+n-1 positions; re-prefill of base+n tokens lands
        cache_index = base+n and emits token n) — and restart the chunk
        state machine.  Penalty counts reset with the slot (documented
        recompute-path limitation; the swap path restores them exactly).
        Loop thread only; the request holds no slot."""
        if req.tokens:
            req.prompt = np.concatenate(
                [req.prompt[:req.base_prompt_len],
                 np.asarray(req.tokens, np.int32)])
        if self.prefix_cache:
            # Re-key over the extended prompt: the resumed request can
            # re-map its own previously registered blocks if they still
            # live in the prefix cache.
            req.prefix_keys = chain_block_keys(req.prompt, self.block_size)
        req.next_prefill_offset = 0
        req.prefill_chunks = 0
        req.prefix_cached = 0
        req.prefill_idle = 0
        req.prefill_started_at = None

    def _preempt(self, req: _SlotRequest) -> None:
        """Evict ``req`` from its slot under block pressure (loop thread;
        the caller already flushed any in-flight launch, so this runs at
        an iteration boundary with every emitted token on host).

        Swap path: leading SHARED blocks (prefix-cache refcounts or
        registrations — their bytes stay reachable through the cache)
        are never moved, only counted; the private suffix's bytes gather
        to the host tier when the cost model prefers the PCIe round-trip
        over re-running prefill.  Recompute path: nothing moves, the
        request's history folds into its prompt.  Either way the slot's
        device residency tears down exactly like ``_retire`` — blocks
        freed, table row to trash — and the request parks in
        ``_preempted`` for ranked re-admission."""
        slot = req.slot
        shard = self._slot_shard[slot]
        blocks = list(self._slot_blocks[slot])
        was_prefilling = req.prefilling()
        backlog_left = len(req.prompt) - req.next_prefill_offset
        swapped_bytes = -1
        if self._tier_pool is not None and req.tokens and not was_prefilling:
            written = req.base_prompt_len + len(req.tokens) - 1
            live = min(self.paged.blocks_for(written), len(blocks))
            shared_n = 0
            while (shared_n < live
                   and self._allocator.is_shared(blocks[shared_n])):
                shared_n += 1
            private = blocks[shared_n:live]
            per_block = self.kv_hbm_bytes // max(1, self.paged.num_blocks)
            if self._tier_pool.policy.prefer_swap(
                    len(private) * per_block, written):
                entry = self._tier_pool.swap_out(
                    self._cache, rid=req.rid, private_blocks=private,
                    shared_blocks=shared_n, written=written,
                    last_token=req.tokens[-1],
                    generation=req.gen.generation,
                    counts=self._counts, slot=slot)
                swapped_bytes = entry.bytes
        if swapped_bytes < 0:
            self._requeue_recompute(req)
        if blocks:
            self._allocator.free(blocks)
            self._slot_blocks[slot] = []
        self._block_tables[slot, :] = self._allocator.trash_block(shard)
        self._dev_block_tables = None  # host table reset
        self._fresh[slot] = False
        self._turnover.pop(slot, None)  # freed without a retirement
        if self._tracer.recording:
            self._tracer.add_instant(
                "preempt", cat="serve", tid=req.rid,
                args={"request_id": req.rid, "slot": slot,
                      "path": "swap" if swapped_bytes >= 0
                      else "recompute",
                      "swap_bytes": max(swapped_bytes, 0)})
        if self._lifecycle is not None:
            self._lifecycle.record(
                req.rid, "PREEMPTED",
                path="swap" if swapped_bytes >= 0 else "recompute",
                swap_bytes=max(swapped_bytes, 0),
                tokens=len(req.tokens))
        with self._lock:
            self._reserved[shard] -= req.reserved_blocks
            req.reserved_blocks = 0
            if req.gen is not None:
                # Unpin the generation: the parked request re-pins at
                # resume (swap payloads carry their generation tag and
                # invalidate on mismatch).
                req.gen.refs -= 1
                if req.gen is not self._gen and req.gen.refs == 0:
                    req.gen.params = None
                req.gen = None
            if was_prefilling:
                self._prefilling -= 1
                self._prefill_backlog -= backlog_left
                self._obs["prefilling_slots"].set(self._prefilling)
                self._obs["prefill_backlog"].set(self._prefill_backlog)
            self._active.pop(slot, None)
            self._free.append(slot)
            req.slot = -1
            req.preemptions += 1
            self._preemptions += 1
            if swapped_bytes >= 0:
                self._preempt_swapped += 1
                self._obs["swap_out_bytes"].inc(swapped_bytes)
            else:
                self._preempt_recompute += 1
            self._preempted.append(req)
            self._obs["preemptions"].inc()
            self._note_active_locked()
            self._cond.notify_all()
        logger.debug(
            "preempted request %d from slot %d (%s, %d token(s) emitted)",
            req.rid, slot, "swap" if swapped_bytes >= 0 else "recompute",
            len(req.tokens))

    def _resume_swapped(self, req: _SlotRequest, entry) -> bool:
        """Restore a swap-parked request into its freshly claimed slot:
        re-acquire the shared prefix chain by key, allocate private
        blocks and scatter the parked bytes back (donated cache rebound
        through each program), rebind the block-table row, reset the
        slot's index rows to the preemption-time written count, and
        restore the penalty counts row — byte-exact resume, no prefill.
        Returns False (after resetting the request for recompute) when
        the shared chain was evicted while parked."""
        slot = req.slot
        shard = self._slot_shard[slot]
        shared: List[int] = []
        if entry.shared_blocks:
            shared = self._allocator.acquire_prefix(
                req.prefix_keys[:entry.shared_blocks], shard)
            if len(shared) < entry.shared_blocks:
                if shared:
                    self._allocator.free(shared)
                self._tier_pool.drop(req.rid)
                self._requeue_recompute(req)
                return False
        fresh: List[int] = []
        if entry.payloads:
            fresh = self._allocator.allocate(
                len(entry.payloads), slot=slot, shard=shard)
            self._cache = self._tier_pool.swap_in(
                self._cache, rid=req.rid, blocks=fresh)
        self._counts = self._tier_pool.restore_counts(
            self._counts, rid=req.rid, slot=slot)
        blocks = shared + fresh
        if blocks:
            self._block_tables[slot, :len(blocks)] = blocks
        self._dev_block_tables = None  # host table changed
        self._slot_blocks[slot] = list(blocks)
        self._cache = self.engine.bind_slot_rows(
            self._cache, [slot], [entry.written])
        self._last_tok[slot, 0] = entry.last_token
        if self.async_decode:
            self._fresh[slot] = True
        else:
            self._dev_last_tok = None  # host vector is newer
        req.next_prefill_offset = len(req.prompt)  # not prefilling
        self._turnover.pop(slot, None)  # taken without an admission
        if self._tracer.recording:
            self._tracer.add_instant(
                "resume_swap", cat="serve", tid=req.rid,
                args={"request_id": req.rid, "slot": slot,
                      "swap_bytes": int(entry.bytes),
                      "shared_blocks": int(entry.shared_blocks)})
        self._tier_pool.take(req.rid)
        with self._lock:
            release = min(req.reserved_blocks, len(blocks))
            req.reserved_blocks -= release
            self._reserved[shard] -= release
            self._admitted += 1
            self._resumes_swapped += 1
            self._active[slot] = req
            self._obs["admissions"].inc()
            self._obs["swap_in_bytes"].inc(int(entry.bytes))
            self._note_active_locked()
        if self._lifecycle is not None:
            self._lifecycle.record(
                req.rid, "RESUMED", path="swap",
                swap_bytes=int(entry.bytes))
        logger.debug(
            "resumed request %d into slot %d by swap restore "
            "(%d shared + %d private block(s))",
            req.rid, slot, len(shared), len(fresh))
        return True

    def _ensure_blocks(self, req: _SlotRequest, tokens_written: int) -> None:
        """Allocate-on-boundary-cross: grow the slot's block list (and its
        block-table row) to cover ``tokens_written`` positions, consuming
        the request's admission reservation.  Reservations make this
        infallible for admitted requests."""
        if self.paged is None:
            return
        blocks = self._slot_blocks[req.slot]
        needed = self.paged.blocks_for(tokens_written)
        self._cover_window_ring(req.slot, needed)
        if needed <= len(blocks):
            return
        shard = self._slot_shard[req.slot]
        fresh = self._allocator.allocate(
            needed - len(blocks), slot=req.slot, shard=shard)
        self._block_tables[req.slot, len(blocks):needed] = fresh
        self._dev_block_tables = None  # host table grew
        blocks.extend(fresh)
        self._note_index_blocks_held()
        with self._lock:
            release = min(req.reserved_blocks, len(fresh))
            req.reserved_blocks -= release
            self._reserved[shard] -= release

    def _cover_window_ring(self, slot: int, covered: int) -> None:
        """The window pool's side of ``_ensure_blocks``: the row now covers
        ``covered`` blocks of positions.  Ring entries are mapped as the
        row first reaches them and no further; past the ring a block's
        positions go over the entry that slid out of the window, counted
        as recycled, and nothing is taken or copied."""
        ring = self.paged.window_ring
        if not ring:
            return
        with self._lock:
            seen = self._window_covered[slot]
        if covered <= seen:
            return
        held, want = min(seen, ring), min(covered, ring)
        if want > held:
            first = 1 + slot * ring
            self._block_tables[
                slot, self._full_cols + held:self._full_cols + want] = (
                np.arange(first + held, first + want))
            self._dev_block_tables = None  # host table grew
        recycled = max(0, covered - max(seen, ring))
        self._note_window_covered(slot, covered, recycled)
        if recycled:
            self._obs["window_recycled"].inc(recycled)

    def _note_window_covered(self, slot: int, covered: int,
                             recycled: int = 0) -> None:
        """``stats()`` reads the rows' coverage from other threads: it
        changes under the lock, and the gauges follow it."""
        with self._lock:
            self._window_covered[slot] = covered
            self._window_recycled += recycled
            window = self._window_blocks_held_locked()
        self._obs["kv_blocks_held"].labels(kind="full").set(
            self._allocator.used_count)
        self._obs["kv_blocks_held"].labels(kind="window").set(window)

    def _note_active_locked(self) -> None:
        """The resident set changed.  Call under ``_lock``."""
        self._obs["active_slots"].set(len(self._active))
        if self._state_bytes:
            self._obs["state_bytes_held"].set(
                len(self._active) * self._state_bytes)

    def _note_index_blocks_held(self) -> None:
        """Where index keys lie in a second pool under the latent pool's
        table, a block the allocator hands out is one block of each: the
        gauge says so by kind (beside the ring's, where there is one)."""
        if self._kv_geometry.get("index_block_bytes"):
            for kind in ("latent", "index"):
                self._obs["kv_blocks_held"].labels(kind=kind).set(
                    self._allocator.used_count)

    def _window_blocks_held_locked(self) -> int:
        """Call under ``_lock``."""
        ring = self.paged.window_ring
        return sum(min(c, ring) for c in self._window_covered)

    def _two_pool_stats_locked(self) -> Dict[str, float]:
        """What the rows hold in each kind of pool (call under ``_lock``);
        nothing for a family with one.  Each thing the geometry declares
        adds its keys: a recurrent state, index keys under the table, a
        window ring.  ``kv_bytes_held`` is the table's blocks at what one
        holds on the layers under the table, and the ring's at what one
        holds on the window layers; ``kv_bytes_held_uniform`` is what the
        same rows would hold if every layer kept a block wherever the
        full layers do (one geometry for all)."""
        if self.paged is None:
            return {}
        g = self._kv_geometry
        iters = self._iterations
        held = self._allocator.used_count
        bytes_held = held * g.get(
            "full_block_bytes",
            self.block_size * g.get("bytes_per_token", 0))
        out: Dict[str, float] = {}
        if self._state_bytes:
            # Bytes a slot (the recurrent state) beside bytes a token (the
            # K/V layers' pool, in ``kv_bytes_held``).
            out.update({
                "state_bytes_per_slot": float(self._state_bytes),
                # Rows a decode launch ran, mean: each reads and writes
                # its state once a step.
                "state_slots_live": (self._occupancy_sum / iters
                                     if iters else 0.0),
                "state_bytes_held": float(
                    len(self._active) * self._state_bytes),
                "state_resets": float(self._state_resets),
            })
        if g.get("index_block_bytes"):
            # Index keys beside the latent pool, under one table: a block
            # held is one of each.
            out.update({
                "kv_blocks_held_latent": float(held),
                "kv_blocks_held_index": float(held),
                "kv_bytes_held_index": float(held * g["index_block_bytes"]),
                # The full indexer layers score ``decode_live_positions``;
                # the layers that attend under a selection read the same
                # rows' latents, each row counted up to the selection.
                "decode_selected_positions": (
                    self._live_selected_positions_sum / iters
                    if iters else 0.0),
            })
        if self.paged.window_ring:
            window = self._window_blocks_held_locked()
            out.update({
                "kv_blocks_held_full": float(held),
                "kv_blocks_held_window": float(window),
                "kv_bytes_held_uniform": float(
                    bytes_held + held * g["window_block_bytes"]),
                "window_ring_blocks": float(self.paged.window_ring),
                "window_blocks_recycled": float(self._window_recycled),
                # The full layers read ``decode_live_positions``; the
                # window layers the same rows, each counted up to the
                # window.
                "decode_live_positions_window": (
                    self._live_window_positions_sum / iters
                    if iters else 0.0),
            })
            bytes_held += window * g["window_block_bytes"]
        if out:
            out["kv_bytes_held"] = float(bytes_held)
        return out

    def _paged_call_kwargs(self) -> Dict[str, Any]:
        """Paged kwargs for the slot programs, with the block tables kept
        DEVICE-resident: the replicated copy is re-put only after a host
        table mutation (``_dev_block_tables`` invalidated), not per
        launch.  Loop-thread only, like every table mutator."""
        if self.paged is None:
            return {}
        if self._dev_block_tables is None:
            self._dev_block_tables = self.engine.put_replicated(
                self._block_tables)
        return {"paged": self.paged, "block_tables": self._dev_block_tables}

    def _map_prefix(self, req: _SlotRequest) -> int:
        """Map the longest cached prefix into ``req``'s slot (loop thread,
        outside the lock — same discipline as ``_ensure_blocks``).  Bumps
        the hit blocks' refcounts, writes them into the slot's table row,
        releases the matching admission reservations, and returns the
        block-aligned position prefill starts from (0 on a miss).

        The chain is re-walked HERE, at map time, not trusted from any
        earlier peek: an eviction between pick and map (another admit in
        the same batch allocating under pressure) must shorten the hit,
        never resurrect a reallocated block."""
        if not self.prefix_cache or not req.prefix_keys:
            return 0
        # Never map the whole prompt: prefill must compute >= 1 position,
        # so a block-aligned prompt recomputes its last block (COW).
        cacheable = self.paged.prefix_blocks(len(req.prompt))
        if cacheable <= 0:
            return 0
        shard = self._slot_shard[req.slot]
        blocks = self._allocator.acquire_prefix(
            req.prefix_keys[:cacheable], shard)
        m = len(blocks)
        if m:
            self._block_tables[req.slot, :m] = blocks
            self._dev_block_tables = None  # host table changed
            self._slot_blocks[req.slot].extend(blocks)
        start = m * self.block_size
        with self._lock:
            self._prefix_hits += m
            self._prefix_misses += cacheable - m
            if m:
                release = min(req.reserved_blocks, m)
                req.reserved_blocks -= release
                self._reserved[shard] -= release
                self._prefix_tokens_skipped += start
                self._obs["prefix_hits"].inc(m)
                self._obs["prefix_skipped"].observe(start)
            if cacheable - m:
                self._obs["prefix_misses"].inc(cacheable - m)
        return start

    def _register_prefix(self, req: _SlotRequest) -> None:
        """After prefill: publish the slot's FULL prompt blocks (now
        holding their final K/V — decode appends strictly past the
        prompt) under their chained keys.  Idempotent for the blocks that
        were themselves mapped from cache."""
        if not self.prefix_cache or not req.prefix_keys:
            return
        full = len(req.prompt) // self.block_size
        if full <= 0:
            return
        self._allocator.register_prefix(
            self._slot_blocks[req.slot][:full], req.prefix_keys[:full],
            self._slot_shard[req.slot])

    def _turnover_prefilled(self, req: _SlotRequest, t: float) -> None:
        """The successor's prefill launch has ended (its first token is
        host-visible): the slot now waits only for a decode launch."""
        turn = self._turnover.get(req.slot)
        if turn is not None and turn.rid_in == req.rid:
            turn.prefilled_at = t

    def _turnover_launched(self, slots, t: float) -> None:
        """A decode launch over ``slots`` starts at ``t``: close the
        turnover of every slot whose successor it carries for the first
        time.  The span runs from the retirement to this launch and its
        three waits (for the iteration's end, for the prefill launch, for
        this launch) sum to its length."""
        if not self._turnover:
            return
        for slot in slots:
            turn = self._turnover.get(slot)
            if turn is None or turn.prefilled_at is None:
                continue
            del self._turnover[slot]
            self._obs["slot_turnover"].observe(t - turn.retired_at)
            self._tracer.add_span(
                "slot_turnover", cat="serve", tid=slot,
                start=turn.retired_at, end=t,
                args={"wait_iteration_s": turn.admitted_at - turn.retired_at,
                      "wait_prefill_s": turn.prefilled_at - turn.admitted_at,
                      "wait_launch_s": t - turn.prefilled_at,
                      "rid_out": turn.rid_out, "rid_in": turn.rid_in,
                      "queued_at_retire": turn.queued_at_retire})

    def _admit(self, admits: List[_SlotRequest]) -> None:
        """Admission: map the cached prefix, init the chunk state machine
        and make the request RESIDENT.  No prefill compute runs here —
        ``_prefill_step`` spends the iteration's budget on the resident
        prefilling slots (with ``prefill_budget=0`` the whole prompt runs
        as a single chunk in the same iteration, the classic one-shot
        behaviour).  The worst-case block reservation was already taken
        under the loop lock — once, at admit — so chunk-boundary
        allocations can never fail mid-prefill."""
        for req in admits:
            admitted_at = _now()
            queue_wait_s = admitted_at - req.submitted
            if self._tracer.recording:
                self._tracer.add_span(
                    "queue_wait", cat="serve", tid=req.rid,
                    start=req.submitted, end=admitted_at,
                    args={"request_id": req.rid, "slot": req.slot})
                # Finish the per-rid flow the gateway started: Perfetto
                # draws the arrow from the gateway's lane into this
                # request's scheduler lane.
                self._tracer.add_flow(
                    "request", id=req.rid, phase="f", cat="serve",
                    tid=req.rid, t=admitted_at)
                if req.blocked_since is not None:
                    self._tracer.add_span(
                        "reservation_wait", cat="serve", tid=req.rid,
                        start=req.blocked_since, end=admitted_at,
                        args={"request_id": req.rid,
                              "reserved_blocks": req.reserved_blocks})
            # Prefix-cached tokens cost ZERO prefill budget: the chunk
            # walk starts past the mapped blocks.
            start = self._map_prefix(req)
            req.next_prefill_offset = start
            req.prefix_cached = start
            req.prefill_started_at = admitted_at
            turn = self._turnover.get(req.slot)
            if turn is not None:
                turn.admitted_at, turn.rid_in = admitted_at, req.rid
            with self._lock:
                self._admitted += 1
                self._active[req.slot] = req
                self._prefilling += 1
                self._prefill_backlog += len(req.prompt) - start
                self._queue_wait_ms.append(queue_wait_s * 1000.0)
                self._obs["admissions"].inc()
                self._obs["queue_wait"].observe(queue_wait_s)
                self._note_active_locked()
                self._obs["prefilling_slots"].set(self._prefilling)
                self._obs["prefill_backlog"].set(self._prefill_backlog)
            if self._lifecycle is not None:
                self._lifecycle.record(
                    req.rid, "ADMITTED", t=admitted_at, slot=req.slot,
                    prefix_cached=start,
                    readmission=req.preemptions)
            logger.debug("admitted request into slot %d (prompt %d, "
                         "cached %d)", req.slot, len(req.prompt), start)

    def _sampling_vector(self, decoding: Dict[int, _SlotRequest]):
        """Full (num_slots,) per-slot sampling vectors for a decode /
        megastep / verify launch: each occupied slot's own SamplingParams
        at its emitted-token count (the seeded-key step index); idle rows
        pad as greedy, the cheapest row of the shared program.  Loop
        thread only — reads request state the loop owns."""
        params: List[Optional[sampling_lib.SamplingParams]] = (
            [None] * self.num_slots)
        steps = [0] * self.num_slots
        for slot, req in decoding.items():
            params[slot] = req.sampling
            steps[slot] = len(req.tokens)
        return sampling_lib.pack(params, steps)

    def _prefill_step(self) -> None:
        """Spend up to ``prefill_budget`` prompt tokens on the resident
        slots still prefilling, in ``chunk_priority`` order (new requests
        first — one small chunk starts a short decoding while a whale's
        remaining chunks overlap it — with an aging bound so the whale
        can't starve).  Each slot runs at most one ``min(remaining,
        budget)``-token chunk per iteration via
        ``prefill_into_slots(start_offsets=[offset])`` — the offset is a
        dynamic argument, so chunk N reuses chunk N-1's compiled program
        whenever the lengths match.  A chunk that would overrun the
        iteration's remaining budget WAITS (no partial chunks, so the
        compiled-shape set stays the canonical chunk sizes); the walk
        still offers the leftover budget to later, smaller chunks.  The
        FINAL chunk's output token is the request's first generated token
        — earlier chunks' outputs predict prompt tokens the caller
        already has and are discarded — so TTFT is stamped at the first
        DECODED token, here."""
        with self._lock:
            # Same snapshot discipline as _decode_once: close() clears
            # _active from another thread under the lock.
            snapshot = dict(self._active)
        pending = sorted((r for r in snapshot.values() if r.prefilling()),
                         key=lambda r: r.chunk_priority())
        if not pending:
            return
        budget = self.prefill_budget
        spent = 0
        for req in pending:
            off = req.next_prefill_offset
            remaining = len(req.prompt) - off
            chunk = remaining if budget <= 0 else min(remaining, budget)
            if budget > 0 and spent + chunk > budget:
                req.prefill_idle += 1
                continue
            req.prefill_idle = 0
            chunk_start = _now()
            # Only the FINAL chunk's token is emitted — mid-prefill
            # chunks' outputs are discarded, so only the final chunk
            # commits to the penalty counts.
            final = (off + chunk) >= len(req.prompt)
            with self._tracer.span(
                    "prefill_chunk", cat="serve",
                    args={"request_id": req.rid, "slot": req.slot,
                          "offset": int(off), "chunk_tokens": int(chunk),
                          # Positions the chunk's attention ran against.
                          "context_tokens": int(off + chunk),
                          "chunk_index": int(req.prefill_chunks),
                          "final": bool(final)}):
                self._ensure_blocks(req, off + chunk)
                with grouped_matmul.record_forms(self._moe_forms,
                                                 "slot_prefill"):
                    tok_dev, self._cache, self._counts = (
                        self.engine.prefill_into_slots(
                            self._cache, req.prompt[None, off:off + chunk],
                            [req.slot],
                            sampling=sampling_lib.pack(
                                [req.sampling], [len(req.tokens)]),
                            counts=self._counts, commit=np.array([final]),
                            counter=self._next_counter(),
                            params=req.gen.params,
                            start_offsets=[off] if off else None,
                            **self._paged_call_kwargs()))
                spent += chunk
                if self._state_bytes and not off:
                    # The family starts a row at position 0 from a zero
                    # state, whatever the slot's last occupant left.
                    self._state_resets += 1
                    self._obs["state_resets"].inc()
                req.next_prefill_offset = off + chunk
                req.prefill_chunks += 1
                if (self._lifecycle is not None
                        and self._lifecycle.verbose_loop_events):
                    # Export-only: chunk boundaries colour the JSONL
                    # trace; the fold's prefill phase keys off ADMITTED
                    # -> FIRST_TOKEN alone.
                    self._lifecycle.record(
                        req.rid, "PREFILL_CHUNK", offset=int(off),
                        chunk_tokens=int(chunk),
                        chunk_index=int(req.prefill_chunks - 1))
                first_decoded = False
                deferred = final and self.async_decode
                if deferred:
                    # Defer the first-token fetch into the launch ring:
                    # the chunk's launch interleaves with in-flight decode
                    # fetches instead of blocking the loop mid-iteration.
                    # The slot stays OUT of the decode-active set
                    # (``req.tokens`` empty) until the resolve lands its
                    # token, so no decode launch dispatches it early.
                    # The depth bound applies to deferred chunks too:
                    # several slots finishing prefill in one iteration
                    # must not stack the ring past what the flag promises.
                    self._ring_push(
                        _InflightPrefill(req=req, dispatch_t=chunk_start,
                                         fetch_payload=tok_dev),
                        self.async_depth)
                elif final:
                    tok = int(self._fetch_host(tok_dev)[0])
                    now = _now()
                    self._turnover_prefilled(req, now)
                    # A recompute-resumed request already stamped its
                    # TTFT on its first admission — never restamp.
                    first_decoded = req.first_token_at is None
                    if first_decoded:
                        req.first_token_at = now
                        if self._lifecycle is not None:
                            self._lifecycle.record(
                                req.rid, "FIRST_TOKEN", t=now,
                                chunks=int(req.prefill_chunks))
                    req.last_token_at = now
                    req.tokens.append(tok)
                    self._last_tok[req.slot, 0] = tok
                    self._dev_last_tok = None  # host vector is newer
                    self._register_prefix(req)
                    self._emit_tokens(req)
            if final and self._tracer.recording:
                self._tracer.add_span(
                    "prefill", cat="serve", tid=req.rid,
                    start=req.prefill_started_at, end=_now(),
                    args={"request_id": req.rid, "slot": req.slot,
                          "prompt_len": int(len(req.prompt)),
                          "prefix_tokens_cached": int(
                              req.prefix_cached),
                          "chunks": int(req.prefill_chunks)})
            with self._lock:
                self._prefill_chunks += 1
                self._prefill_backlog -= chunk
                self._obs["prefill_chunk"].observe(chunk)
                if final:
                    self._prefilling -= 1
                    if first_decoded:
                        # Deferred chunks observe TTFT at their ring
                        # resolve instead — when the token actually
                        # became host-visible.
                        self._obs["ttft"].observe(
                            req.first_token_at - req.submitted)
                self._obs["prefilling_slots"].set(self._prefilling)
                self._obs["prefill_backlog"].set(self._prefill_backlog)
            if final and not deferred:
                logger.debug(
                    "slot %d finished prefill (prompt %d, %d chunk(s), "
                    "ttft %.1fms)", req.slot, len(req.prompt),
                    req.prefill_chunks,
                    (req.first_token_at - req.submitted) * 1e3)
                if req.done():  # max_new_tokens == 1 or instant eos
                    self._retire(req)

    def _decode_snapshot(self) -> Dict[int, _SlotRequest]:
        """Slot -> request map of the rows that decode THIS iteration."""
        with self._lock:
            # Snapshot the slot->request map: close() clears self._active
            # under the lock from another thread, so the loop below must
            # not re-read it after this point.
            snapshot = dict(self._active)
        # Slots still prefilling are NOT decode-active: their state
        # advances in _prefill_step, and their cache_index rows must stay
        # frozen at next_prefill_offset (the decode step's inactive-row
        # garbage write lands at that position, which the next chunk
        # overwrites — never in a mapped prefix block, which sits
        # strictly below the offset).  req.tokens is non-empty exactly
        # when the final chunk has run.
        return {s: r for s, r in snapshot.items() if r.tokens}

    def _decode_once(self) -> None:
        """One decode iteration through the launch RING, the only
        plain-decode path: build the dispatch (one ``megastep``-step
        fused program per live generation; K=1 is a megastep of one),
        append its record, then resolve oldest-first until fewer than
        ``depth`` records stay in flight.

        ``depth`` is ``async_depth`` with ``async_decode`` on — the
        device runs up to that many launches ahead of the host view
        (2 = the classic double buffer) — and 1 otherwise: dispatch,
        then resolve, the synchronous loop.  Traffic the stale host view
        cannot serve (``_needs_sync``) drops THIS iteration to depth 1.
        A depth-1 iteration first drains the ring, so the host token
        vector is authoritative when it dispatches.

        The one exception is speculation (``spec_k >= 1``): an iteration
        tries a draft-and-verify launch first — ``_spec_dispatch_async``
        on the ring at depth > 1, the synchronous ``_decode_spec_once``
        at depth 1 — and falls through to the plain dispatch when no
        slot drafted, so a degenerate k=0 verify program is never built
        or cached.  Deferred final prefill chunks ride the same ring."""
        depth = 1
        if self.async_decode:
            if self._needs_sync():
                with self._lock:
                    self._async_fallbacks += 1
            else:
                depth = self.async_depth
        rec = None
        if depth == 1:
            self._flush_inflight()
            if self._fresh.any():
                # Collapse to the sync invariant: with every launch
                # resolved the host token vector is authoritative again.
                self._dev_last_tok = None
                self._fresh[:] = False
            if self.spec_k and self._decode_spec_once():
                return
        elif self.spec_k:
            rec = self._spec_dispatch_async()
        if rec is None:
            rec = self._megastep_dispatch()
        if rec is None:
            # Nothing dispatchable (every live horizon is already in
            # flight, or no row decodes yet): resolve ONE record so the
            # loop still makes progress toward the host view.
            if self._ring:
                self._resolve_next()
            return
        self._ring_push(rec, depth)

    def _ring_push(self, rec, depth: int) -> None:
        """Append a just-dispatched record to the launch ring, then
        resolve oldest-first until fewer than ``depth`` stay in flight.
        At depth 1 the record resolves here and now, so its fetch runs
        inline; deeper rings hand it to the fetch thread."""
        if depth > 1:
            self._enqueue_fetch(rec)
        self._ring.append(rec)
        with self._lock:
            self._ring_depth_hist[len(self._ring)] += 1
            self._obs["ring_depth"].set(len(self._ring))
        while len(self._ring) >= depth:
            self._resolve_next()

    def _megastep_dispatch(self) -> Optional[_InflightMegastep]:
        """Dispatch half of a megastep iteration: build horizons and eos
        rows from the host view MINUS tokens still in flight, launch one
        K-step fused program per live generation, and return the
        in-flight record the fetch half resolves later.  Returns None
        when no row can decode.

        Block tables are precomputed for all K positions up front —
        coverage clamped to the request's admission reservation, so a
        row whose horizon ends mid-megastep never allocates past what
        admission promised (its one past-horizon garbage write lands in
        its own last block or the trash block, behind the frozen index
        either way).

        ASYNC DOUBLE BUFFERING: this half may run with the PREVIOUS
        launch still unfetched.  Per-slot horizons subtract that
        launch's ``pending`` token counts, so no row ever overruns
        ``max_new_tokens`` and a row whose remaining horizon is fully
        in flight sits this launch out.  A row that hit its eos INSIDE
        the in-flight launch is dispatched once more (the host cannot
        know yet); its extra tokens are trimmed at fetch and its K/V
        writes stay inside its own reserved coverage, behind the index
        reset of the slot's next prefill — the donation-fencing
        invariant.  Rows whose prefill finished while the launch was in
        flight carry a ``fresh`` flag: their host first token is merged
        into the device token carry ON DEVICE (first launch only — later
        generation groups ride the already-merged carry), so the carry
        chain never round-trips the host.
        """
        prev_pending: Dict[int, int] = {}
        for r in self._ring:
            for slot, n in r.pending.items():
                prev_pending[slot] = prev_pending.get(slot, 0) + n
        decoding = self._decode_snapshot()
        with self._lock:
            K = self.megastep
        horizon = np.zeros((self.num_slots,), np.int32)
        eos_rows = np.full((self.num_slots,), -1, np.int32)
        active_slots: List[int] = []
        live_positions = 0      # cached positions the launched rows hold
        live_window = 0         # ... each row counted up to the window
        live_selected = 0       # ... each row counted up to the selection
        pending: Dict[int, int] = {}
        for slot in sorted(decoding):
            req = decoding[slot]
            inflight = prev_pending.get(slot, 0)
            left = req.max_new_tokens - len(req.tokens) - inflight
            if left <= 0:
                continue  # the rest of the horizon is already in flight
            active_slots.append(slot)
            held = req.base_prompt_len + len(req.tokens) + inflight
            live_positions += held
            live_window += min(held, self._window)
            live_selected += min(held, self._selected)
            pending[slot] = min(K, left)
            horizon[slot] = left
            if req.eos_token is not None:
                eos_rows[slot] = req.eos_token
            # Cover all K upcoming positions once, at megastep start —
            # never past the admission reservation (a short-horizon row
            # stops advancing on device before it would need more).
            self._ensure_blocks(req, megastep_coverage(
                req.base_prompt_len, len(req.tokens) + inflight, K,
                req.max_new_tokens))
        if not active_slots:
            return None
        dispatch_t = _now()
        by_gen: Dict[int, List[int]] = {}
        for slot in active_slots:
            by_gen.setdefault(decoding[slot].gen.generation, []).append(slot)
        with self._tracer.span(
                "dispatch", cat="serve",
                args={"active_slots": len(active_slots),
                      "generations": len(by_gen), "megastep": K}):
            # The megastep carry IS alive-gated, so chaining it through
            # sequential generation groups is exact: group 2's rows ride
            # through group 1's scan untouched, and the final carry holds
            # every row's true last token — a valid device-resident input
            # for the next iteration unconditionally.
            carry = (self._dev_last_tok if self._dev_last_tok is not None
                     else self._last_tok)
            fresh = fresh_tokens = None
            if self._dev_last_tok is not None and self._fresh.any():
                fresh = self._fresh.copy()
                fresh_tokens = self._last_tok[:, 0].copy()
            if self._dev_clock is not None:
                clock = self._dev_clock
            else:
                with self._lock:
                    clock = np.int32(self._device_clock)
            samp = self._sampling_vector(decoding)
            launches: List[Tuple[List[int], Any, Any]] = []
            moe_devs: List[Any] = []    # one a launch, where the model counts
            for generation in sorted(by_gen):
                slots = by_gen[generation]
                active = np.zeros((self.num_slots,), bool)
                active[slots] = True
                with grouped_matmul.record_forms(self._moe_forms,
                                                 "slot_megastep"):
                    (toks_dev, carry, steps_dev, clock, self._cache,
                     self._counts, *moe_dev) = (
                        self.engine.decode_megastep(
                            self._cache, carry, active, horizon, steps=K,
                            eos_rows=eos_rows,
                            sampling=samp, counts=self._counts,
                            counter=self._next_counter(K),
                            params=decoding[slots[0]].gen.params,
                            fresh_tokens=fresh_tokens, fresh=fresh,
                            clock=clock, **self._paged_call_kwargs()))
                fresh = fresh_tokens = None  # the first launch merged them
                launches.append((slots, toks_dev, steps_dev))
                moe_devs.extend(moe_dev)
            self._dev_last_tok = carry
            self._dev_clock = clock
            self._fresh[:] = False
            with self._lock:
                self._iterations += 1
                self._occupancy_sum += len(active_slots)
                self._live_positions_sum += live_positions
                self._live_window_positions_sum += live_window
                self._live_selected_positions_sum += live_selected
                self._last_occupancy = len(active_slots)
                self._note_dispatch_locked(dispatch_t)
                seq = self._launch_seq
        self._dispatch_s.append(_now() - dispatch_t)
        self._turnover_launched(active_slots, dispatch_t)
        if self._lifecycle is not None and self._lifecycle.verbose_loop_events:
            # Loop-level event (rid 0): launch cadence for the JSONL
            # export; the per-request attribution rides the
            # TOKEN_STREAMED context instead.
            self._lifecycle.record(
                0, "MEGASTEP_DISPATCH", t=dispatch_t, steps=int(K),
                active_slots=len(active_slots), seq=int(seq))
        return _InflightMegastep(
            launches=launches, decoding=decoding,
            base_len={s: len(decoding[s].tokens) + prev_pending.get(s, 0)
                      for s in active_slots},
            pending=pending, steps=K, dispatch_t=dispatch_t, seq=seq,
            clock_dev=clock,
            # Device handles only — slots stay host-side in ``launches``
            # (fetched lists round-trip as unhashable 0-d arrays).
            fetch_payload=([(toks_dev, steps_dev)
                            for _, toks_dev, steps_dev in launches],
                           clock, moe_devs))

    def _megastep_fetch(self, rec: _InflightMegastep) -> None:
        """Fetch half: resolve a dispatched megastep — ONE (num_slots, K)
        fetch per launch — then trim, stamp TPOT, and retire at the
        boundary.

        The host trims each row's fetched tokens with the same
        ``req.done()`` walk that retires it, so a row finishing at inner
        step j < K contributes exactly its first j+1 tokens —
        bit-identical to K=1 — and nothing after its eos leaks
        into ``req.tokens``.  A slot that retired at a PREVIOUS fetch
        (its eos was in flight when this launch dispatched) is skipped
        whole: its columns here are the zombie tail the donation fence
        already contains.

        TPOT for K > 1 anchors to the launch's device window via the
        iteration clock: the realized inner-step cadence is
        (fetch - dispatch) / steps_run, and a row's j-th fetched token
        is stamped dispatch + (j+1) cadences — real megastep timing
        per inner step, not an equal share of the host's observation
        gap (which, async, includes a whole iteration of host work)."""
        K = rec.steps
        (outs_host, clock_host, moe_host), fetch_done, waited = (
            self._rec_result(rec))
        fetched = [(slots, toks, int(steps))
                   for (slots, _, _), (toks, steps)
                   in zip(rec.launches, outs_host)]
        clock_now = int(clock_host)
        if self._tracer.recording:
            self._tracer.add_span(
                "launch", cat="serve", tid=_LAUNCH_LANE,
                start=rec.dispatch_t, end=fetch_done,
                args={"megastep": K, "launches": len(rec.launches)})
        if self._lifecycle is not None and self._lifecycle.verbose_loop_events:
            self._lifecycle.record(
                0, "MEGASTEP_FETCH", t=fetch_done, steps=int(K),
                seq=int(rec.seq), wait_s=round(waited, 6))
        span = max(fetch_done - rec.dispatch_t, 0.0)
        gaps: List[float] = []
        appended = 0
        effective = 0
        lc_batch = [] if self._lifecycle is not None else None
        to_retire: List[_SlotRequest] = []
        for slots, toks, steps_run in fetched:
            effective += steps_run
            per_step = span / max(steps_run, 1)
            for slot in slots:
                req = rec.decoding[slot]
                if req.finished_at is not None:
                    continue  # retired at an earlier fetch: zombie tail
                n = 0
                for j in range(K):
                    if req.done():
                        break  # trim the dead row's tail columns
                    req.tokens.append(int(toks[slot, j]))
                    n += 1
                    t_emit = rec.dispatch_t + (j + 1) * per_step
                    if req.last_token_at is not None:
                        gaps.append(
                            max(t_emit - req.last_token_at, 0.0) * 1e3)
                    req.last_token_at = t_emit
                appended += n
                if n:
                    self._last_tok[slot, 0] = req.tokens[-1]
                    self._emit_tokens(
                        req, t=fetch_done, dispatch_t=rec.dispatch_t,
                        wait_s=waited, batch=lc_batch)
                if req.done():
                    to_retire.append(req)
        # Flush deferred TOKEN_STREAMED folds BEFORE retiring: RETIRED
        # finalizes a request's fold, so its last tokens must land first.
        if lc_batch:
            self._lifecycle.record_tokens_batch(
                lc_batch, t=fetch_done, dispatch_t=rec.dispatch_t,
                wait_s=waited)
        for req in to_retire:
            self._retire(req)
        self._step_s.append(span / max(effective, 1))
        with self._lock:
            self._device_clock = clock_now
            self._tpot_gaps_ms.extend(gaps)
            self._megastep_launches += len(rec.launches)
            self._megastep_tokens += appended
            self._megastep_effective_steps += effective
            for _ in rec.launches:
                self._obs["megastep_size"].observe(K)
            saved = appended - len(rec.launches)
            if saved > 0:
                self._obs["megastep_amortized"].inc(saved)
            for rows in moe_host:
                self._note_moe_counts_locked(np.asarray(rows, np.int64))
            self._note_fetch_done_locked(rec.seq, fetch_done)
            self._obs["device_idle"].set(self._idle_fraction_locked())

    def _note_moe_counts_locked(self, rows: np.ndarray) -> None:
        """One decode launch's ``moe_counts`` rows: (expert layers, experts
        held + 3), the last three columns the assignments to experts held
        elsewhere, the held experts that got a token (summed over steps)
        and the steps counted."""
        if self._moe_counts is None:
            self._moe_counts = np.zeros_like(rows)
        self._moe_counts += rows
        self._obs["moe_assignments"].labels(held="here").inc(
            int(rows[:, :-3].sum()))
        self._obs["moe_assignments"].labels(held="absent").inc(
            int(rows[:, -3].sum()))

    def _moe_stats_locked(self) -> Dict[str, float]:
        """The expert layers' load as the decode launches counted it; all
        zero for a model that counts nothing."""
        keys = ("moe_experts_held", "moe_assignments_here",
                "moe_assignments_absent", "moe_active_experts_per_step",
                "moe_layer_steps", "moe_load_max_over_mean")
        if self._moe_counts is None:
            return dict.fromkeys(keys, 0.0)
        tokens = self._moe_counts[:, :-3].astype(np.float64)
        absent, active, steps = (self._moe_counts[:, -3:].sum(axis=0)
                                 .astype(np.float64))
        loaded = tokens[tokens.sum(axis=1) > 0]
        return dict(zip(keys, (
            float(tokens.shape[1]), float(tokens.sum()), float(absent),
            float(active / steps) if steps else 0.0, float(steps),
            # Per layer, the busiest held expert's tokens over the held
            # experts' mean; the layers' mean.
            float(np.mean(loaded.max(axis=1) / loaded.mean(axis=1)))
            if len(loaded) else 0.0)))

    def _fetch_host(self, value):
        """THE loop thread's host-fetch point for launch outputs: one
        explicit ``jax.device_get`` — already an ndarray, no extra
        ``np.asarray`` round-trip — so every host sync in the hot loop
        routes through a single sanctioned helper, inside the ``fetch``
        span: the loop blocked on the device."""
        with self._tracer.span("fetch", cat="serve"):
            return jax.device_get(value)

    def _flush_inflight(self) -> None:
        """Resolve EVERY in-flight launch, oldest first.  The barrier
        for every path that needs the host view current: mode switches
        back to sync, autotune re-picking K, cancellation, drain, and
        loop exit."""
        while self._ring:
            self._resolve_next()

    def _resolve_next(self) -> None:
        """Resolve the OLDEST in-flight ring record — launch order is
        resolve order, unconditionally, so admission/retire bookkeeping
        trues up in exactly the order the device ran."""
        rec = self._ring.popleft()
        with self._lock:
            self._obs["ring_depth"].set(len(self._ring))
        if isinstance(rec, _InflightPrefill):
            self._prefill_fetch(rec)
        elif isinstance(rec, _InflightSpec):
            self._spec_fetch(rec)
        else:
            self._megastep_fetch(rec)

    def _rec_result(self, rec) -> Tuple[Any, float, float]:
        """A ring record's host payload, its fetch-done timestamp, and
        the loop-thread seconds THIS resolve spent blocked on the fetch
        thread (0.0 on the inline path) — the per-record share of
        ``async_fetch_wait_s``, which the lifecycle fold attributes to
        the resolving requests as ``fetch_wait``.

        Enqueued records resolve on the fetch thread: block on the
        record's Future — accounting the wait, the residual fetch
        latency the overlap did NOT hide — and re-raise any device
        error here on the loop thread, where the loop-death handler
        fails the outstanding request futures.  Records never handed to
        the fetch thread fetch inline (the flush paths on a
        just-constructed record).  Loop thread only; never called while
        holding the scheduler lock (the Future wait would invert the
        lock order against the fetch thread's result hand-back)."""
        if rec.enqueued:
            with self._tracer.span("fetch", cat="serve"):
                t0 = _now()
                out, t_done = rec.fetched.result()
                waited = _now() - t0
            with self._lock:
                self._fetch_wait_s += waited
            return out, t_done, waited
        return self._fetch_host(rec.fetch_payload), _now(), 0.0

    def _enqueue_fetch(self, rec) -> None:
        """Hand a just-dispatched record to the fetch thread (lazily
        started — sync schedulers never pay for it)."""
        if self._fetch_thread is None:
            self._fetch_thread = threading.Thread(
                target=self._fetch_worker,
                name=self._thread.name + "-fetch", daemon=True)
            self._fetch_thread.start()
        rec.enqueued = True
        self._fetch_q.put(rec)

    def _fetch_worker(self) -> None:
        """Fetch-thread main: one blocking ``jax.device_get`` per ring
        record, strictly in launch order (the queue preserves it).  The
        device executes launches in dispatch order, so waiting on record
        N's outputs never races record N+1's compute.  A device_get is
        NOT a launch — it joins the device stream read-only — so this
        thread never takes the engine launch lock; the record's Future
        is its only channel back to the loop thread.  Errors resolve the
        Future exceptionally and re-raise at the loop's resolve."""
        while True:
            rec = self._fetch_q.get()
            if rec is None:
                return
            try:
                # Not ``_fetch_host``: that span is the LOOP thread
                # blocked on the device; this wait is the overlap.
                rec.fetched.set_result(
                    (jax.device_get(rec.fetch_payload), _now()))
            except BaseException as e:  # noqa: BLE001 — rethrown at resolve
                rec.fetched.set_exception(e)

    def _needs_sync(self) -> bool:
        """Rows the ring's stale-by-up-to-D-iterations host view cannot
        serve, so their iteration runs at depth 1: multiple live
        generations chain grouped launches (the fetch order would
        interleave with the next dispatch), and SEEDED
        sampling folds ``len(req.tokens)`` into its per-row key (a stale
        step would replay keys).  Greedy rows ignore the RNG entirely
        and unseeded sampled rows draw from the global per-launch
        counter — fresh every dispatch — so both stay async-safe.
        Speculative decoding COMPOSES now: drafts come from the stale
        fetched view (staleness only costs acceptance length) and the
        chain verify scores against the device-resident carry, so the
        emitted targets stay exactly the sequential tokens."""
        with self._lock:
            reqs = [r for r in self._active.values() if r.tokens]
        gens = set()
        for req in reqs:
            if req.sampling is not None and req.sampling.seed is not None:
                return True
            gens.add(req.gen.generation)
        return len(gens) > 1

    def _note_dispatch_locked(self, t: float) -> None:
        """Device-idle accounting at dispatch: close the open
        fetch-to-dispatch gap (time the device sat with no launch in
        flight) and advance the launch sequence."""
        if self._window_start is None:
            self._window_start = t
        if self._await_gap_from is not None:
            self._idle_gap_s += max(0.0, t - self._await_gap_from)
            self._await_gap_from = None
        self._launch_seq += 1

    def _note_fetch_done_locked(self, seq: int, t: float) -> None:
        """Device-idle accounting at fetch: when NO newer launch was
        dispatched after this one (sync mode, or an async drain), the
        device idles from here until the next dispatch — open the gap.
        Async steady state dispatches N+1 before fetching N, so the
        sequence check keeps the gap closed."""
        self._window_end = t
        if self._launch_seq == seq:
            self._await_gap_from = t

    def _idle_fraction_locked(self) -> float:
        """Idle gap time over the first-dispatch .. last-fetch window."""
        if self._window_start is None or self._window_end is None:
            return 0.0
        window = self._window_end - self._window_start
        if window <= 0.0:
            return 0.0
        return min(1.0, self._idle_gap_s / window)

    def _autotune_eval(self) -> None:
        """One autotune control step (``megastep='auto'``): pick K from
        the measured host-dispatch vs device-step times, then FREEZE.

        The dispatch cost ``a`` amortizes over K inner device steps of
        ``b`` seconds each; K is the smallest power of two keeping the
        host half under half the device window (a <= K*b/2, i.e.
        K >= 2a/b), clamped to [1, _AUTOTUNE_MAX_K].  Powers of two
        keep the compiled-program set tiny and the pick stable under
        timing noise; freezing at the first confident pick guarantees
        no steady-state recompiles."""
        if (len(self._dispatch_s) < _AUTOTUNE_MIN_SAMPLES
                or len(self._step_s) < _AUTOTUNE_MIN_SAMPLES):
            return
        a = sum(self._dispatch_s) / len(self._dispatch_s)
        b = max(sum(self._step_s) / len(self._step_s), 1e-9)
        target = 2.0 * a / b
        k = 1
        while k < target and k < _AUTOTUNE_MAX_K:
            k *= 2
        with self._lock:
            k_changed = k != self.megastep
        if k_changed:
            self._flush_inflight()  # the old-K launch resolves first
        with self._lock:
            self._autotune_frozen = True
            self.megastep = k
        logger.info(
            "megastep autotune: froze K=%d (dispatch %.3f ms, inner "
            "step %.3f ms)", k, a * 1e3, b * 1e3)

    def _draft_for(self, req: _SlotRequest,
                   inflight: int = 0) -> Optional[np.ndarray]:
        """n-gram prompt-lookup drafter: match the request's last n tokens
        (n from ``spec_ngram`` down to 1) against earlier occurrences in
        its OWN prompt + generated history and propose the continuation
        after the LATEST match — up to ``spec_k`` tokens, clamped so the
        drafts plus the guaranteed bonus token never exceed the horizon
        (MINUS ``inflight`` tokens other launches may still emit — the
        async ring budgets worst case, so under-drafting is the safe
        side).  Pure host-side numpy; returns None when nothing matches
        (or the horizon leaves no room for even one draft), which is
        what lets a draft-less iteration fall through to the plain
        step."""
        k = min(self.spec_k,
                req.max_new_tokens - len(req.tokens) - inflight - 1)
        if k < 1:
            return None
        if req.tokens:
            ctx = np.concatenate(
                [req.prompt, np.asarray(req.tokens, np.int32)])
        else:
            ctx = req.prompt
        L = len(ctx)
        for n in range(min(self.spec_ngram, L - 1), 0, -1):
            pat = ctx[L - n:]
            win = np.lib.stride_tricks.sliding_window_view(ctx, n)
            # Exclude the pattern's own (final) window: a self-match
            # proposes nothing and would shadow a genuine earlier hit.
            hits = np.flatnonzero((win[:-1] == pat).all(axis=1))
            if hits.size:
                # Latest hit with room for a FULL k-token continuation;
                # otherwise the hit with the longest continuation (ties
                # -> latest).  Plain ``hits[-1]`` degenerates on
                # period-<=n loops: the latest occurrence sits at the
                # very end of the context and proposes a 1-token draft
                # when the history supports k.
                room = np.minimum(L - (hits + n), k)
                full = hits[room >= k]
                i = int(full[-1]) if full.size else int(
                    hits[len(hits) - 1 - np.argmax(room[::-1])])
                cont = ctx[i + n:i + n + k]
                if cont.size:
                    return np.asarray(cont, np.int32)
        return None

    def _decode_spec_once(self) -> bool:
        """One draft-and-verify iteration: ONE (num_slots, spec_k + 1)
        verify forward per live generation scores the last token plus
        every slot's padded drafts; each row keeps its longest agreeing
        draft prefix plus one bonus/correction target (1 .. spec_k + 1
        tokens) and advances its cache index by exactly the kept length.
        Returns False — no launch, no program build — when NO slot
        drafted this iteration; the caller falls through to the plain
        step or the megastep.

        The verify program is cached per (spec_k, temp, top_k, paged)
        only: drafts shorter than ``spec_k`` are zero-padded and masked
        via ``draft_lens``, so varying draft lengths never recompile.

        RNG counters: the launch reserves ``spec_k + 1`` consecutive
        counters (position j samples with ``counter + j`` — the exact
        counters the sequential loop would burn for those tokens) and,
        when the iteration was a single launch, REFUNDS the unconsumed
        tail, so a single sampled stream's counter sequence is identical
        spec on vs off (token-identical streams, the sampled-parity
        oracle).  Multi-launch iterations skip the refund: concurrent
        generations interleave counters either way, and every target is
        still a fresh-key categorical draw from the correct conditional
        (distribution-exact)."""
        decoding = self._decode_snapshot()
        active_slots = list(decoding)
        if not active_slots:
            return False
        drafts: Dict[int, np.ndarray] = {}
        for slot in active_slots:
            d = self._draft_for(decoding[slot])
            if d is not None:
                drafts[slot] = d
        if not drafts:
            return False  # fall through: never build a k=0 verify
        K = self.spec_k
        iter_start = _now()
        tokens_in = np.zeros((self.num_slots, K + 1), np.int32)
        tokens_in[:, 0] = self._last_tok[:, 0]
        draft_lens = np.zeros((self.num_slots,), np.int32)
        for slot, d in drafts.items():
            tokens_in[slot, 1:1 + d.size] = d
            draft_lens[slot] = d.size
        for slot in active_slots:
            # Cover every position this launch may write (last token +
            # accepted drafts), clamped to the admission reservation.
            req = decoding[slot]
            self._ensure_blocks(req, spec_coverage(
                req.base_prompt_len, len(req.tokens),
                int(draft_lens[slot]), req.max_new_tokens))
        by_gen: Dict[int, List[int]] = {}
        for slot in active_slots:
            by_gen.setdefault(decoding[slot].gen.generation, []).append(slot)
        samp = self._sampling_vector(decoding)
        launches: List[Tuple[List[int], Any, Any]] = []
        with self._tracer.span(
                "dispatch", cat="serve",
                args={"active_slots": len(active_slots),
                      "generations": len(by_gen), "spec_k": K,
                      "drafted": int(draft_lens.sum())}):
            for generation in sorted(by_gen):
                slots = by_gen[generation]
                active = np.zeros((self.num_slots,), bool)
                active[slots] = True
                targets_dev, accepted_dev, self._cache, self._counts = (
                    self.engine.verify_slots(
                        self._cache, tokens_in, active, draft_lens,
                        sampling=samp, counts=self._counts,
                        counter=self._next_counter(K + 1),
                        params=decoding[slots[0]].gen.params,
                        **self._paged_call_kwargs()))
                launches.append((slots, targets_dev, accepted_dev))
        self._turnover_launched(active_slots, iter_start)
        # The next iteration's input token is the per-slot LAST kept
        # target — host-assembled from the fetch below, so the device
        # token chain breaks here by design.
        self._dev_last_tok = None
        with self._lock:
            self._iterations += 1
            self._occupancy_sum += len(active_slots)
            self._last_occupancy = len(active_slots)
            self._note_dispatch_locked(iter_start)
            spec_seq = self._launch_seq
        fetched = [(slots, self._fetch_host(targets_dev),
                    self._fetch_host(accepted_dev))
                   for slots, targets_dev, accepted_dev in launches]
        with self._lock:
            self._note_fetch_done_locked(spec_seq, _now())
        step_done = _now()
        gaps: List[float] = []
        emitted_per_slot: List[int] = []
        appended = 0
        accepted_total = 0
        consumed = 1
        lc_batch = [] if self._lifecycle is not None else None
        to_retire = []
        for slots, targets, accepted in fetched:
            for slot in slots:
                req = decoding[slot]
                acc = int(accepted[slot])
                n = 0
                for j in range(acc + 1):
                    if req.done():
                        break  # eos mid-acceptance trims the tail
                    req.tokens.append(int(targets[slot, j]))
                    n += 1
                appended += n
                accepted_total += min(acc, n)
                consumed = max(consumed, n)
                emitted_per_slot.append(n)
                self._last_tok[slot, 0] = req.tokens[-1]
                if n and req.last_token_at is not None:
                    per = (step_done - req.last_token_at) * 1000.0 / n
                    gaps.extend([per] * n)
                req.last_token_at = step_done
                if n:
                    self._emit_tokens(
                        req, t=step_done, dispatch_t=iter_start,
                        batch=lc_batch)
                if req.done():
                    to_retire.append(req)
        if lc_batch:
            self._lifecycle.record_tokens_batch(
                lc_batch, t=step_done, dispatch_t=iter_start)
        for req in to_retire:
            self._retire(req)
        drafted_total = int(draft_lens.sum())
        with self._lock:
            if len(launches) == 1:
                # Refund the counters the launch reserved but no slot's
                # emitted token consumed: the next iteration resumes at
                # exactly the counter the sequential loop would be at.
                self._decode_counter -= (K + 1) - consumed
            self._tpot_gaps_ms.extend(gaps)
            # A verify launch IS a decode launch: the steps-per-token
            # surface (launches vs tokens fetched) spans both paths.
            self._megastep_launches += len(launches)
            self._megastep_tokens += appended
            self._spec_launches += len(launches)
            self._spec_drafted += drafted_total
            self._spec_accepted += accepted_total
            self._spec_emitted += appended
            self._obs["spec_drafted"].inc(drafted_total)
            self._obs["spec_accepted"].inc(accepted_total)
            if drafted_total:
                self._obs["spec_accept_rate"].observe(
                    accepted_total / drafted_total)
            for n in emitted_per_slot:
                if n:
                    self._obs["spec_accepted_len"].observe(n)
            saved = appended - len(launches)
            if saved > 0:
                self._obs["megastep_amortized"].inc(saved)
        return True

    def _spec_dispatch_async(self) -> Optional[_InflightSpec]:
        """Dispatch half of an ASYNC speculative iteration: draft every
        live row from the stale fetched view, launch ONE chain-verify
        program (single live generation — ``_needs_sync`` already routed
        mixed generations to sync), and return the ring record.  Returns
        None when no slot drafted, so the caller falls through to the
        megastep dispatch and a degenerate k=0 verify is never built.

        Horizons budget WORST CASE against the ring (draft_len + 1 per
        in-flight spec launch): acceptance below the worst case only
        means this dispatch under-drafts — the conservative side, never
        an overrun past ``max_new_tokens``.  RNG counters: the launch
        reserves ``spec_k + 1`` counters like the sync path but never
        refunds the unconsumed tail (the consumed count is unknown until
        resolve, and later launches have drawn their own ranges by
        then).  Greedy rows ignore counters entirely — the parity
        surface — and unseeded sampled rows remain distribution-exact,
        same as the sync multi-launch case."""
        prev_pending: Dict[int, int] = {}
        for r in self._ring:
            for slot, n in r.pending.items():
                prev_pending[slot] = prev_pending.get(slot, 0) + n
        decoding = self._decode_snapshot()
        drafts: Dict[int, np.ndarray] = {}
        active_slots: List[int] = []
        pending: Dict[int, int] = {}
        for slot in sorted(decoding):
            req = decoding[slot]
            inflight = prev_pending.get(slot, 0)
            left = req.max_new_tokens - len(req.tokens) - inflight
            if left <= 0:
                continue  # the rest of the horizon is already in flight
            active_slots.append(slot)
            d = self._draft_for(req, inflight)
            if d is not None:
                drafts[slot] = d
            # Draft-less rows still ride the launch (their bonus target
            # advances them one token, like the sync verify).
            pending[slot] = (d.size if d is not None else 0) + 1
        if not drafts:
            return None  # fall through: never build a k=0 verify
        K = self.spec_k
        dispatch_t = _now()
        tokens_in = np.zeros((self.num_slots, K + 1), np.int32)
        # Column 0 is dead weight in chain mode — the device substitutes
        # the carry — but fill it so the host array stays well-formed.
        tokens_in[:, 0] = self._last_tok[:, 0]
        draft_lens = np.zeros((self.num_slots,), np.int32)
        for slot, d in drafts.items():
            tokens_in[slot, 1:1 + d.size] = d
            draft_lens[slot] = d.size
        for slot in active_slots:
            # Cover every position this launch may write (carry target +
            # accepted drafts) PAST the worst-case in-flight tokens,
            # clamped to the admission reservation.
            req = decoding[slot]
            self._ensure_blocks(req, spec_coverage(
                req.base_prompt_len,
                len(req.tokens) + prev_pending.get(slot, 0),
                int(draft_lens[slot]), req.max_new_tokens))
        active = np.zeros((self.num_slots,), bool)
        active[active_slots] = True
        # Same carry/fresh/clock chaining contract as the megastep
        # dispatch: device-resident when a launch already ran, host
        # vectors otherwise.
        carry = (self._dev_last_tok if self._dev_last_tok is not None
                 else self._last_tok[:, 0])
        fresh = fresh_tokens = None
        if self._dev_last_tok is not None and self._fresh.any():
            fresh = self._fresh.copy()
            fresh_tokens = self._last_tok[:, 0].copy()
        if self._dev_clock is not None:
            clock = self._dev_clock
        else:
            with self._lock:
                clock = np.int32(self._device_clock)
        samp = self._sampling_vector(decoding)
        with self._tracer.span(
                "dispatch", cat="serve",
                args={"active_slots": len(active_slots), "spec_k": K,
                      "drafted": int(draft_lens.sum())}):
            (targets_dev, accepted_dev, carry_out, clock_out, self._cache,
             self._counts) = self.engine.verify_slots(
                self._cache, tokens_in, active, draft_lens,
                sampling=samp, counts=self._counts,
                counter=self._next_counter(K + 1),
                params=decoding[active_slots[0]].gen.params,
                chain=True, carry=carry, fresh_tokens=fresh_tokens,
                fresh=fresh, clock=clock, **self._paged_call_kwargs())
        self._turnover_launched(active_slots, dispatch_t)
        launches = [(active_slots, targets_dev, accepted_dev)]
        self._dev_last_tok = carry_out
        self._dev_clock = clock_out
        self._fresh[:] = False
        with self._lock:
            self._iterations += 1
            self._occupancy_sum += len(active_slots)
            self._last_occupancy = len(active_slots)
            self._note_dispatch_locked(dispatch_t)
            seq = self._launch_seq
        return _InflightSpec(
            launches=launches, decoding=decoding, pending=pending,
            draft_lens={s: int(draft_lens[s]) for s in active_slots},
            k=K, dispatch_t=dispatch_t, seq=seq, clock_dev=clock_out,
            fetch_payload=([(targets_dev, accepted_dev)], clock_out))

    def _spec_fetch(self, rec: _InflightSpec) -> None:
        """Fetch half: resolve a dispatched chain-verify launch — the
        same ``req.done()`` trim walk, TPOT stamping, and boundary
        retirement as the sync spec path, one ring position later.  A
        slot that retired at an earlier fetch is skipped whole (zombie
        tail — the megastep fetch's contract)."""
        (outs_host, clock_host), fetch_done, waited = self._rec_result(rec)
        fetched = [(slots, targets, accepted)
                   for (slots, _, _), (targets, accepted)
                   in zip(rec.launches, outs_host)]
        clock_now = int(clock_host)
        if self._tracer.recording:
            self._tracer.add_span(
                "launch", cat="serve", tid=_LAUNCH_LANE,
                start=rec.dispatch_t, end=fetch_done,
                args={"spec_k": rec.k, "launches": len(rec.launches)})
        gaps: List[float] = []
        emitted_per_slot: List[int] = []
        appended = 0
        accepted_total = 0
        lc_batch = [] if self._lifecycle is not None else None
        to_retire = []
        for slots, targets, accepted in fetched:
            for slot in slots:
                req = rec.decoding[slot]
                if req.finished_at is not None:
                    continue  # retired at an earlier fetch: zombie tail
                acc = int(accepted[slot])
                n = 0
                for j in range(acc + 1):
                    if req.done():
                        break  # eos mid-acceptance trims the tail
                    req.tokens.append(int(targets[slot, j]))
                    n += 1
                appended += n
                accepted_total += min(acc, n)
                emitted_per_slot.append(n)
                if n:
                    self._last_tok[slot, 0] = req.tokens[-1]
                    if req.last_token_at is not None:
                        per = ((fetch_done - req.last_token_at)
                               * 1000.0 / n)
                        gaps.extend([per] * n)
                    req.last_token_at = fetch_done
                    self._emit_tokens(
                        req, t=fetch_done, dispatch_t=rec.dispatch_t,
                        wait_s=waited, batch=lc_batch)
                if req.done():
                    to_retire.append(req)
        if lc_batch:
            self._lifecycle.record_tokens_batch(
                lc_batch, t=fetch_done, dispatch_t=rec.dispatch_t,
                wait_s=waited)
        for req in to_retire:
            self._retire(req)
        drafted_total = sum(rec.draft_lens.values())
        with self._lock:
            self._device_clock = clock_now
            self._tpot_gaps_ms.extend(gaps)
            # A verify launch IS a decode launch, same as the sync path.
            self._megastep_launches += len(rec.launches)
            self._megastep_tokens += appended
            self._spec_launches += len(rec.launches)
            self._spec_drafted += drafted_total
            self._spec_accepted += accepted_total
            self._spec_emitted += appended
            self._obs["spec_drafted"].inc(drafted_total)
            self._obs["spec_accepted"].inc(accepted_total)
            if drafted_total:
                self._obs["spec_accept_rate"].observe(
                    accepted_total / drafted_total)
            for n in emitted_per_slot:
                if n:
                    self._obs["spec_accepted_len"].observe(n)
            saved = appended - len(rec.launches)
            if saved > 0:
                self._obs["megastep_amortized"].inc(saved)
            self._note_fetch_done_locked(rec.seq, fetch_done)
            self._obs["device_idle"].set(self._idle_fraction_locked())

    def _prefill_fetch(self, rec: _InflightPrefill) -> None:
        """Resolve a deferred final prefill chunk: the request's first
        decoded token lands HERE — at its ring position — instead of at
        a blocking mid-iteration device_get that would have waited out
        every launch queued ahead of it on the device stream.  TTFT and
        TTFB stamp at resolve (when the token actually became host-
        visible); the slot joins the decode-active set at the NEXT
        dispatch via the fresh-row merge."""
        host, fetch_done, _waited = self._rec_result(rec)
        req = rec.req
        if req.finished_at is not None:
            return  # retired while the chunk was in flight
        self._turnover_prefilled(req, fetch_done)
        tok = int(host[0])
        # A recompute-resumed request already stamped its TTFT on its
        # first admission — never restamp.
        first_decoded = req.first_token_at is None
        if first_decoded:
            req.first_token_at = fetch_done
            if self._lifecycle is not None:
                self._lifecycle.record(
                    req.rid, "FIRST_TOKEN", t=fetch_done,
                    chunks=int(req.prefill_chunks), deferred=True)
        req.last_token_at = fetch_done
        req.tokens.append(tok)
        self._last_tok[req.slot, 0] = tok
        # Keep the device carry (launches may be in flight); the next
        # dispatch merges this row from the host vector on device via
        # the fresh-row mask.
        self._fresh[req.slot] = True
        self._register_prefix(req)
        self._emit_tokens(req)
        if first_decoded:
            with self._lock:
                self._obs["ttft"].observe(
                    req.first_token_at - req.submitted)
        logger.debug(
            "slot %d finished prefill (prompt %d, %d chunk(s), "
            "ttft %.1fms)", req.slot, len(req.prompt),
            req.prefill_chunks,
            (req.first_token_at - req.submitted) * 1e3)
        if req.done():  # max_new_tokens == 1 or instant eos
            self._retire(req)

    def _next_counter(self, count: int = 1) -> int:
        """Reserve ``count`` consecutive in-step RNG counters and return
        the FIRST — the megastep folds ``counter + j`` in per inner step,
        burning exactly the per-token counters K launches of one would."""
        with self._lock:
            self._decode_counter += count
            return self._decode_counter - count + 1

    def _emit_tokens(self, req: _SlotRequest, *,
                     t: Optional[float] = None,
                     dispatch_t: Optional[float] = None,
                     wait_s: float = 0.0,
                     batch: Optional[List] = None) -> None:
        """Deliver ``req``'s not-yet-streamed tokens to its ``on_token``
        callback (loop thread, right after each host fetch appends them).

        The cancel flag and the streamed high-water mark are read and
        advanced under the scheduler lock — once ``cancel()`` flips the
        flag, no further tokens ever reach the callback — but the
        callback itself runs OUTSIDE the lock: it hands off to a stream
        queue owned by another thread, and holding the non-reentrant
        scheduler lock across foreign code invites deadlock.  TTFB is
        stamped at the first delivery (for every request, streaming or
        not — the non-streaming TTFB is what a gateway client would have
        seen).

        ``t``/``dispatch_t``/``wait_s`` are the lifecycle fold's launch
        context from the resolving fetch site: the tokens' landing time,
        the launch's dispatch time, and the loop-thread seconds the
        resolve blocked on the fetch thread.  All host values the caller
        already had — the fold splits the request's progress gap into
        decode_compute / fetch_wait / scheduler_stall from them.  Loop
        sites that resolve several slots in one fetch pass ``batch`` (a
        list): the lifecycle record is deferred to ONE
        ``record_tokens_batch`` call after the loop, so the recorder's
        lock is paid per fetch, not per slot."""
        with self._lock:
            if req.cancelled:
                return
            new = req.tokens[req.streamed:]
            if not new:
                return
            first = req.streamed == 0
            req.streamed = len(req.tokens)
            if first:
                ttfb_s = _now() - req.submitted
                self._ttfb_ms.append(ttfb_s * 1e3)
                self._obs["ttfb"].observe(ttfb_s)
            cb = req.on_token
        if self._lifecycle is not None:
            if batch is not None:
                batch.append((req.rid, len(new)))
            else:
                self._lifecycle.record_tokens(
                    req.rid, t=t, n=len(new), dispatch_t=dispatch_t,
                    wait_s=wait_s)
        if cb is None:
            return
        try:
            cb(list(new))
        except Exception:  # noqa: BLE001 — stream delivery must not kill decode
            logger.exception(
                "on_token callback failed for request %d; disabling "
                "stream delivery (the request still completes)", req.rid)
            req.on_token = None

    def _retire(self, req: _SlotRequest) -> None:
        with self._tracer.span(
                "retire", cat="serve",
                args={"request_id": req.rid, "slot": req.slot}):
            self._retire_body(req)

    def _retire_body(self, req: _SlotRequest) -> None:
        req.finished_at = _now()
        if req.first_token_at is not None and self._tracer.recording:
            self._tracer.add_span(
                "decode", cat="serve", tid=req.rid,
                start=req.first_token_at, end=req.finished_at,
                args={"request_id": req.rid, "slot": req.slot,
                      "tokens": int(len(req.tokens))})
        if self.paged is not None:
            # Bulk-free the slot's blocks and point its table row back at
            # its shard's trash block BEFORE the slot can go inactive —
            # the shared decode step's garbage writes for idle rows must
            # never land in a reallocated block.
            blocks = self._slot_blocks[req.slot]
            used = len(blocks)
            if blocks:
                self._allocator.free(blocks)
                self._slot_blocks[req.slot] = []
            self._block_tables[req.slot, :] = self._allocator.trash_block(
                self._slot_shard[req.slot])
            self._dev_block_tables = None  # host table reset
            if self.paged.window_ring:      # the ring's entries too
                self._note_window_covered(req.slot, 0)
            self._note_index_blocks_held()
        else:
            used = self.paged_equivalent_blocks
        with self._lock:
            was_cancelled = req.cancelled
            if self.paged is not None:
                self._reserved[self._slot_shard[req.slot]] -= (
                    req.reserved_blocks)
                req.reserved_blocks = 0
            if req.gen is not None:
                req.gen.refs -= 1
                if req.gen is not self._gen and req.gen.refs == 0:
                    # Last in-flight request on a superseded generation:
                    # drop the params reference so device buffers free.
                    req.gen.params = None
            if req.prefilling():
                # Only a cancelled request retires mid-prefill: give its
                # unspent prompt tokens back to the backlog gauges.
                self._prefilling -= 1
                self._prefill_backlog -= (
                    len(req.prompt) - req.next_prefill_offset)
                self._obs["prefilling_slots"].set(self._prefilling)
                self._obs["prefill_backlog"].set(self._prefill_backlog)
            self._blocks_per_request.append(used)
            self._blocks_hist[used] += 1
            self._active.pop(req.slot, None)
            self._free.append(req.slot)
            queued = len(self._queue)
            self._retired += 1
            self._obs["retirements"].inc()
            self._note_active_locked()
            if was_cancelled:
                self._cancelled += 1
                self._obs["cancelled"].inc()
            else:
                self._completed += 1
                self._obs["completed"].inc()
                self._obs["request"].observe(
                    req.finished_at - req.submitted)
                self._latencies_ms.append(
                    (req.finished_at - req.submitted) * 1e3)
                dl = (req.sampling.deadline_ms
                      if req.sampling is not None else None)
                if dl is not None:
                    # TTFT-deadline goodput: a completion counts as good
                    # when its FIRST token landed inside deadline_ms.
                    met = (req.first_token_at is not None
                           and (req.first_token_at - req.submitted)
                           * 1000.0 <= dl)
                    if met:
                        self._deadline_met += 1
                        self._obs["deadline_met"].inc()
                    else:
                        self._deadline_missed += 1
                        self._obs["deadline_missed"].inc()
                if req.first_token_at is not None:
                    self._ttft_ms.append(
                        (req.first_token_at - req.submitted) * 1e3)
                    if len(req.tokens) > 1:
                        self._tpot_ms.append(
                            (req.finished_at - req.first_token_at) * 1e3
                            / (len(req.tokens) - 1))
                        self._obs["tpot"].observe(
                            (req.finished_at - req.first_token_at)
                            / (len(req.tokens) - 1))
            # Wake drain() waiters when the last resident slot retires.
            self._cond.notify_all()
        # A turnover opens only with a request waiting: a slot that stands
        # empty for want of traffic is not one.
        if queued:
            self._turnover[req.slot] = _Turnover(
                req.finished_at, req.rid, queued)
        else:
            self._turnover.pop(req.slot, None)
        if self._lifecycle is not None:
            self._lifecycle.record(
                req.rid, "CANCELLED" if was_cancelled else "RETIRED",
                t=req.finished_at, tokens=len(req.tokens),
                preemptions=req.preemptions)
        if req.gen is not None:
            # Generation tag rides the Future: callers (and the fleet
            # hot-reload tests) can assert which weights produced this
            # stream.  Set BEFORE the result so no waiter observes a
            # resolved future without its tag.
            req.future.generation = req.gen.generation
        # These Futures are never RUNNING (no executor), so a client may
        # legally ``cancel()`` them directly at any moment before the
        # result lands.  ``set_running_or_notify_cancel`` closes that
        # window: once it returns True the future is RUNNING and
        # ``set_result`` cannot be raced; False means a cancel already
        # won.  A swept cancel resolves the same way — ``result()``
        # raises ``CancelledError``.
        if not was_cancelled and req.future.set_running_or_notify_cancel():
            req.future.set_result(np.asarray(req.tokens, np.int32))
        else:
            req.future.cancel()
