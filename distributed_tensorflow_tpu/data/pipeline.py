"""Input pipeline: per-host sharding and device prefetch.

Behavioral model: ``tf.distribute``'s distributed input (SURVEY.md §3.4):
``DistributedDataset`` ($TF/python/distribute/input_lib.py:729) splits a
tf.data pipeline across workers with ``AutoShardPolicy`` (FILE/DATA), and
per-replica iterators feed each device.  TPU-native translation:

- Each *host* produces only its slice of the global batch (DATA auto-shard ≡
  ``index=process_index, num_shards=process_count``).
- ``jax.make_array_from_process_local_data`` assembles the global sharded
  array — the host→device boundary.
- A small prefetch queue keeps the device fed (the role of tf.data's
  prefetch-to-device), so input never serializes with the step.

Sources are plain Python iterators of numpy dicts; tf.data or grain can slot
in front unchanged (anything yielding numpy batches works).
"""

from __future__ import annotations

import collections
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, Iterator, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding

Batch = Dict[str, np.ndarray]


# Stream-sharding override (set by train_lib from the ACTUAL batch layout):
# None = the default one-shard-per-process policy.  Needed because a
# multi-process mesh whose batch dim is NOT process-partitioned (e.g. a
# context-only mesh: batch replicated, sequence sharded) requires every
# host to feed the SAME stream — per-process decorrelated streams would
# assemble an inconsistent "replicated" array with no error anywhere.
_stream_override: Optional[tuple] = None


def set_stream_shard_override(num_shards: Optional[int],
                              index: Optional[int] = None) -> None:
    """Pin (num_shards, index) for every subsequent ``shard_options()``
    call in this process; ``set_stream_shard_override(None)`` clears."""
    global _stream_override
    _stream_override = None if num_shards is None else (num_shards, index)


def shard_options(num_shards: Optional[int] = None, index: Optional[int] = None):
    """The DATA AutoShardPolicy parameters for this host."""
    if num_shards is None and _stream_override is not None:
        return _stream_override
    return (
        num_shards if num_shards is not None else jax.process_count(),
        index if index is not None else jax.process_index(),
    )


def host_batch_layout(sharding, global_batch_size: int):
    """(host_rows, num_stream_shards, stream_index) from the REAL layout of
    the batch dim across processes.

    Derived from ``sharding.devices_indices_map`` on the batch dim: each
    process feeds exactly the rows its devices own.  Classic DP (batch
    split over processes) gives (B/P, P, process_index) — identical to
    ``per_host_batch_size`` + default shard_options.  A batch dim NOT
    partitioned across processes (context/model-parallel-only meshes)
    gives (B, 1, 0): every host feeds the full, identical stream.
    """
    me = jax.process_index()
    imap = sharding.devices_indices_map((global_batch_size,))
    per_proc: Dict[int, set] = {}
    for d, idx in imap.items():
        sl = idx[0]
        start = sl.start or 0
        stop = sl.stop if sl.stop is not None else global_batch_size
        per_proc.setdefault(d.process_index, set()).add((start, stop))

    def block(p):
        spans = sorted(per_proc[p])
        lo, hi = spans[0][0], spans[-1][1]
        covered = sum(b - a for a, b in spans)
        if covered != hi - lo:
            raise ValueError(
                f"process {p} owns non-contiguous batch rows {spans} under "
                f"{sharding}; the host data stream cannot express this "
                "layout — use a batch sharding whose process blocks are "
                "contiguous")
        return lo, hi

    blocks = {p: block(p) for p in per_proc}
    distinct = sorted(set(blocks.values()))
    sizes = {b - a for a, b in distinct}
    if len(sizes) != 1:
        raise ValueError(
            f"uneven per-process batch blocks {distinct} under {sharding}; "
            "the host data stream assumes equal shards")
    lo, hi = blocks[me]
    return hi - lo, len(distinct), distinct.index((lo, hi))


def per_host_batch_size(global_batch_size: int) -> int:
    n = jax.process_count()
    if global_batch_size % n:
        raise ValueError(
            f"global_batch_size {global_batch_size} not divisible by "
            f"{n} processes"
        )
    return global_batch_size // n


def make_global_batches(
    host_iter: Iterable[Batch], sharding: NamedSharding
) -> Iterator[Dict[str, jax.Array]]:
    """Assemble per-host numpy batches into global sharded jax.Arrays."""
    for batch in host_iter:
        yield {
            k: jax.make_array_from_process_local_data(sharding, v)
            for k, v in batch.items()
        }


class DevicePrefetchIterator:
    """Background prefetch of sharded batches (prefetch-to-device) with a
    parallel transfer stage.

    Two-stage pipeline, both off the training thread:

    1. A producer thread pulls numpy batches from ``host_iter`` and submits
       one ``make_array_from_process_local_data`` job *per batch key* to a
       shared thread pool — key transfers of one batch run concurrently,
       and with ``prefetch_depth`` > 1 so do the transfers of consecutive
       batches (the pool is shared across in-flight batches).
    2. The consumer (``__next__``) pops entries in submission order —
       ordering is guaranteed by the queue, not by transfer completion —
       and resolves the per-key futures (re-raising any transfer error).

    Backpressure: the producer blocks once ``prefetch_depth`` batches are
    in flight.  ``stats()`` exports queue-depth and wait-time counters so
    input/compute overlap is observable (``obs.PrefetchMonitorHook``), not
    assumed.  Supports the context-manager protocol; ``close()`` joins the
    producer thread and shuts the pool down.
    """

    def __init__(
        self,
        host_iter: Iterable[Batch],
        sharding: NamedSharding,
        prefetch: int = 2,
        *,
        transfer_workers: int = 2,
    ):
        self._host_iter = iter(host_iter)
        self._sharding = sharding
        self._queue: collections.deque = collections.deque()
        self._capacity = max(1, prefetch)
        self._lock = threading.Condition()
        self._done = False
        self._error: Optional[BaseException] = None
        # Counters (under self._lock): prove or disprove overlap.
        self._enqueued = 0
        self._dequeued = 0
        self._producer_wait_s = 0.0
        self._consumer_wait_s = 0.0
        self._transfer_workers = max(1, transfer_workers)
        self._pool = ThreadPoolExecutor(
            max_workers=self._transfer_workers,
            thread_name_prefix="dtt-transfer",
        )
        # Registry bridge: the monitor hook reads this namespace instead of
        # scraping the iterator directly.  Lazy import — obs pulls in
        # training.loop, and data.pipeline must stay importable first.
        from distributed_tensorflow_tpu.obs import metrics as obs_metrics
        from distributed_tensorflow_tpu.obs import trace as obs_trace

        self._tracer = obs_trace.default_tracer()
        self._obs_registry = obs_metrics.default_registry()
        self.obs_namespace = self._obs_registry.register_stats(
            "prefetch", self.stats)
        self._thread = threading.Thread(target=self._fill, daemon=True)
        self._thread.start()

    def _transfer_one(self, value: np.ndarray):
        return jax.make_array_from_process_local_data(self._sharding, value)

    def _fill(self):
        try:
            for batch in self._host_iter:
                # Submit all key transfers before taking the queue lock so
                # the copies overlap the consumer's work immediately.
                futures = {
                    k: self._pool.submit(self._transfer_one, v)
                    for k, v in batch.items()
                }
                with self._lock:
                    t0 = time.perf_counter()
                    while len(self._queue) >= self._capacity and not self._done:
                        self._lock.wait()
                    self._producer_wait_s += time.perf_counter() - t0
                    if self._done:
                        for f in futures.values():
                            f.cancel()
                        return
                    self._queue.append(futures)
                    self._enqueued += 1
                    self._lock.notify_all()
        except BaseException as e:  # surfaced on next()
            with self._lock:
                self._error = e
                self._lock.notify_all()
        finally:
            with self._lock:
                self._done = True
                self._lock.notify_all()

    def __iter__(self):
        return self

    def __next__(self):
        with self._tracer.span("prefetch_wait", cat="data"):
            return self._next_staged()

    def _next_staged(self):
        with self._lock:
            t0 = time.perf_counter()
            while not self._queue and not self._done and self._error is None:
                self._lock.wait()
            self._consumer_wait_s += time.perf_counter() - t0
            # Drain successfully-staged batches before surfacing a source
            # error: batches already in the queue are valid work.
            if self._queue:
                futures = self._queue.popleft()
                self._dequeued += 1
                self._lock.notify_all()
            elif self._error is not None:
                e, self._error = self._error, None
                raise e
            else:
                raise StopIteration
        # Resolve outside the lock: the producer keeps filling while the
        # consumer waits on (usually already-finished) transfers.
        return {k: f.result() for k, f in futures.items()}

    def stats(self) -> Dict[str, float]:
        """Overlap counters (obs export): queue depth, totals, wait times."""
        with self._lock:
            return {
                "queue_depth": float(len(self._queue)),
                "capacity": float(self._capacity),
                "enqueued": float(self._enqueued),
                "dequeued": float(self._dequeued),
                "producer_wait_s": self._producer_wait_s,
                "consumer_wait_s": self._consumer_wait_s,
                "transfer_workers": float(self._transfer_workers),
            }

    def close(self):
        if self.obs_namespace:
            self._obs_registry.unregister_stats(self.obs_namespace)
            self.obs_namespace = None
        with self._lock:
            self._done = True
            # Unblock the producer and drop queued work so join() is fast.
            for futures in self._queue:
                for f in futures.values():
                    f.cancel()
            self._queue.clear()
            self._lock.notify_all()
        if self._thread is not threading.current_thread():
            self._thread.join(timeout=30.0)
        self._pool.shutdown(wait=False)

    def __enter__(self) -> "DevicePrefetchIterator":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


# -- synthetic datasets for the five reference workloads ---------------------

def synthetic_image_classification(
    *,
    batch_size: int,
    image_size: tuple = (28, 28, 1),
    num_classes: int = 10,
    seed: int = 0,
    dtype=np.float32,
    holdout: bool = False,
) -> Iterator[Batch]:
    """Deterministic synthetic (image, label) stream, per-host decorrelated.

    Stands in for MNIST/ImageNet when real data is unavailable (zero-egress
    environments); the label depends on the image so the model can actually
    learn — loss decrease is a real end-to-end signal, not noise.
    """
    num_shards, index = shard_options()
    # holdout: a disjoint noise/label stream over the SAME task (templates
    # unchanged) — the eval split.
    rng = np.random.RandomState(seed * 1009 + index + (500_009 if holdout else 0))
    # Class templates are seed-derived but host-independent so every host
    # draws from the same distribution (only the noise/labels differ).
    tmpl_rng = np.random.RandomState(seed)
    templates = tmpl_rng.randn(num_classes, *image_size).astype(np.float32)
    while True:
        y = rng.randint(0, num_classes, size=(batch_size,)).astype(np.int32)
        noise = rng.randn(batch_size, *image_size).astype(np.float32)
        x = (0.7 * templates[y] + noise).astype(dtype)
        yield {"image": x, "label": y}


def synthetic_lm(
    *,
    batch_size: int,
    seq_len: int,
    vocab_size: int,
    seed: int = 0,
    holdout: bool = False,
) -> Iterator[Batch]:
    """Synthetic token stream with local structure (next-token ≈ f(prev))."""
    num_shards, index = shard_options()
    rng = np.random.RandomState(seed * 2003 + index + (500_009 if holdout else 0))
    while True:
        start = rng.randint(0, vocab_size, size=(batch_size, 1))
        steps = rng.randint(1, 7, size=(batch_size, seq_len))
        tokens = (start + np.cumsum(steps, axis=1)) % vocab_size
        yield {"tokens": tokens.astype(np.int32)}


def mlm_max_predictions(seq_len: int, mask_rate: float = 0.15) -> int:
    """The reference's ``max_predictions_per_seq``: fixed prediction-slot
    count so the MLM head runs on a static (B, K) gather, not (B, T)."""
    return max(1, int(seq_len * mask_rate))


def synthetic_mlm(
    *,
    batch_size: int,
    seq_len: int,
    vocab_size: int,
    mask_token: int = 1,
    mask_rate: float = 0.15,
    seed: int = 0,
    holdout: bool = False,
) -> Iterator[Batch]:
    """BERT-pretraining-style stream: masked tokens + segment ids + NSP label.

    Tokens have the same local structure as ``synthetic_lm`` so MLM is
    learnable.  Masked positions use the reference's
    ``max_predictions_per_seq`` wire format — exactly K =
    ``mlm_max_predictions(seq_len)`` prediction slots per example
    (``mlm_positions``/``mlm_targets``/``mlm_weights`` of shape (B, K)) —
    so the model's MLM head gathers K positions instead of projecting all
    T positions to the vocabulary.
    """
    num_shards, index = shard_options()
    rng = np.random.RandomState(seed * 3001 + index + (500_009 if holdout else 0))
    half = seq_len // 2
    K = mlm_max_predictions(seq_len, mask_rate)
    positions_idx = np.arange(seq_len)[None, :]
    while True:
        start = rng.randint(2, vocab_size, size=(batch_size, 1))
        steps = rng.randint(1, 7, size=(batch_size, seq_len))
        tokens = (start + np.cumsum(steps, axis=1)) % vocab_size
        tokens = np.maximum(tokens, 2)  # 0=pad, 1=mask reserved
        # NSP: for half the examples, replace the second segment with an
        # unrelated sequence.
        nsp = rng.randint(0, 2, size=(batch_size,))
        rand_seg = rng.randint(2, vocab_size, size=(batch_size, seq_len - half))
        second = np.where(nsp[:, None] == 1, tokens[:, half:], rand_seg)
        tokens = np.concatenate([tokens[:, :half], second], axis=1)
        # Variable lengths (the reference's real wiki batches are padded):
        # length in [half, seq_len]; tokens past it are 0-padding and the
        # input_mask marks validity — attention must not read them.
        lengths = rng.randint(half, seq_len + 1, size=(batch_size, 1))
        input_mask = (positions_idx < lengths).astype(np.int32)
        tokens = np.where(input_mask > 0, tokens, 0)
        segment_ids = ((positions_idx >= half) & (positions_idx < lengths))
        # K distinct masked positions per example, all within the valid
        # length (half >= K guarantees enough candidates): padded slots'
        # sort keys are pushed past every valid slot's.
        sort_keys = rng.rand(batch_size, seq_len) + (input_mask == 0) * 2.0
        positions = np.argsort(sort_keys, axis=1)[:, :K].astype(np.int32)
        targets = np.take_along_axis(tokens, positions, axis=1)
        masked = tokens.copy()
        np.put_along_axis(masked, positions, mask_token, axis=1)
        yield {
            "tokens": masked.astype(np.int32),
            "input_mask": input_mask,
            "mlm_positions": positions,
            "mlm_targets": targets.astype(np.int32),
            "mlm_weights": np.ones((batch_size, K), np.float32),
            "segment_ids": segment_ids.astype(np.int32),
            "nsp_label": nsp.astype(np.int32),
        }


def synthetic_recsys(
    *,
    batch_size: int,
    num_dense: int = 13,
    num_sparse: int = 26,
    vocab_size: int = 100_000,
    seed: int = 0,
    holdout: bool = False,
) -> Iterator[Batch]:
    """DLRM/Wide&Deep-style: dense features + categorical ids + CTR label."""
    num_shards, index = shard_options()
    # The CTR weight vector defines the task: derive it from `seed` alone so
    # train and holdout streams share it, then fork the sample stream.
    task_rng = np.random.RandomState(seed * 4001)
    w_dense = task_rng.randn(num_dense).astype(np.float32)
    rng = np.random.RandomState(seed * 4001 + index + (500_009 if holdout else 0))
    while True:
        dense = rng.randn(batch_size, num_dense).astype(np.float32)
        sparse = rng.randint(0, vocab_size, size=(batch_size, num_sparse))
        score = dense @ w_dense + 0.01 * (sparse.sum(-1) % 7 - 3)
        label = (score > 0).astype(np.float32)
        yield {
            "dense": dense,
            "sparse": sparse.astype(np.int32),
            "label": label,
        }
