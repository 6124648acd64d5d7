"""TPU-native inference subsystem (the north star's "serve heavy traffic"
leg): checkpoint -> sharded inference params -> KV-cache decode / batched
classify, fronted by a dynamic micro-batcher with admission control.

Layers:

- ``engine``: restore + re-shard + jitted forward (``ServeEngine``);
- ``batcher``: request coalescing, bucketed shapes, backpressure
  (``DynamicBatcher`` / ``ServeOverloadedError``); its
  ``iteration_level=True`` mode streams requests to the continuous
  scheduler instead of flushing fixed buckets;
- ``continuous``: Orca-style iteration-level decode scheduling over ONE
  resident KV cache (``ContinuousScheduler``) — admit into free slots,
  one fused decode launch per iteration, retire mid-flight;
- ``paged``: host-side block bookkeeping for ``cache_mode="paged"``
  (``BlockAllocator``) — K/V lives in a fixed pool of blocks reached
  through per-slot block tables, with optional int8 storage
  (``models.PagedKVConfig``);
- ``driver``: the in-process request loop behind ``serve.py`` and the
  benchmark's serving cells (``run_serve`` / ``ServeArgs``);
- ``fleet``: multi-replica serving — ``FleetRouter`` dispatches over N
  ``Replica`` engines by load (queue depth, occupancy, free blocks) and
  ``CheckpointWatcher`` hot-reloads new checkpoint steps without
  dropping in-flight requests;
- ``gateway``: the HTTP/SSE front door (``GatewayServer``) — per-token
  streaming through ``submit(on_token=...)`` and bounded
  ``TokenStream`` queues, client cancellation that frees KV blocks
  mid-decode, and max-inflight admission control answering 429 +
  ``Retry-After``;
- ``obs.ServeMonitorHook`` exports the batcher's/scheduler's counters
  (queue depth, occupancy, TTFT/TPOT).
"""

from distributed_tensorflow_tpu.serve.batcher import (
    DynamicBatcher,
    ServeOverloadedError,
)
from distributed_tensorflow_tpu.serve.continuous import ContinuousScheduler
from distributed_tensorflow_tpu.serve.driver import ServeArgs, run_serve
from distributed_tensorflow_tpu.serve.engine import ServeEngine, pad_rows
from distributed_tensorflow_tpu.serve.fleet import (
    CheckpointWatcher,
    FleetRouter,
    Replica,
)
from distributed_tensorflow_tpu.serve.gateway import (
    GatewayServer,
    TokenStream,
)
from distributed_tensorflow_tpu.serve.paged import (
    BlockAllocator,
    BlockExhaustedError,
)

__all__ = [
    "BlockAllocator",
    "BlockExhaustedError",
    "CheckpointWatcher",
    "ContinuousScheduler",
    "DynamicBatcher",
    "FleetRouter",
    "GatewayServer",
    "Replica",
    "ServeArgs",
    "ServeEngine",
    "ServeOverloadedError",
    "TokenStream",
    "pad_rows",
    "run_serve",
]
