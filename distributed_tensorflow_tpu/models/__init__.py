"""Model families: the five reference workloads (SURVEY.md §3.5).

Each model module exposes ``make_workload(**overrides) -> Workload``; the
registry maps CLI names to factories.  A ``Workload`` bundles everything the
unified ``train.py`` entrypoint needs: the flax module, the loss, a synthetic
per-host data source (real data slots in by replacing ``data_fn``), sharding
rules, and per-workload defaults (batch size, grad accum — e.g. GPT-2's
gradient-accumulation config, BASELINE.json config 5).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax.numpy as jnp

from distributed_tensorflow_tpu.parallel.sharding import ShardingRules

PyTree = Any


@dataclasses.dataclass
class Workload:
    name: str
    module: Any  # flax linen module
    loss_fn: Callable  # (params, batch, rng) -> (loss, aux_dict)
    init_batch: Dict[str, Any]  # tiny batch for module.init / shape eval
    data_fn: Callable[[int], Iterator[Dict[str, Any]]]  # per-host batch iter
    rules: ShardingRules
    batch_size: int  # default global batch size
    grad_accum_steps: int = 1
    clip_grad_norm: Optional[float] = None
    learning_rate: float = 1e-3
    warmup_steps: int = 100
    # key in the batch dict whose leading dim counts "examples" for metrics
    example_key: str = "image"
    # Key of init_batch passed positionally to module.init; None passes the
    # whole init_batch dict (for models that consume the batch directly).
    init_key: Optional[str] = None
    # True if the model carries mutable collections (e.g. BatchNorm
    # batch_stats); switches loss_fn to the StatefulLossFn signature.
    stateful: bool = False
    # Inference-mode loss for evaluation.  For stateful models this must use
    # the running statistics (e.g. BatchNorm use_running_average=True) —
    # reusing the training loss_fn would normalize with per-batch stats.
    # Signature matches loss_fn's (stateful or not); stateful eval fns
    # return (loss, aux, model_state_unchanged).  None: reuse loss_fn
    # (correct only for stateless models whose loss is deterministic-safe).
    eval_loss_fn: Optional[Callable] = None
    # Optional optimizer factory: schedule -> optax.GradientTransformation.
    # None uses the framework default (adamw).
    make_optimizer: Optional[Callable[[Any], Any]] = None
    # Held-out input stream for evaluation (same task, disjoint examples).
    # None falls back to data_fn (eval-on-train; only for quick smoke runs).
    eval_data_fn: Optional[Callable[[int], Iterator[Dict[str, Any]]]] = None
    # Optional host-side staging transform applied when writing record
    # files (data.records): e.g. quantize f32 images to uint8 so the host
    # pipeline (disk, loader memcpy, host->device transfer) moves 4x fewer
    # bytes.  The record schema is derived from to_record(init_batch) when
    # set.  Its inverse, ``from_record``, runs ON DEVICE inside the
    # compiled step (train_lib wraps the loss fns with it) and must be a
    # no-op for batches that never went through staging (dtype check) —
    # the pair keeps the staging mechanism self-contained per workload.
    to_record: Optional[Callable[[Dict[str, Any]], Dict[str, Any]]] = None
    from_record: Optional[Callable[[Dict[str, Any]], Dict[str, Any]]] = None
    # Per-step device-side augmentation (the reference ResNet recipe's
    # random crop + flip — the tf.data map stage of its ImageNet input_fn,
    # moved on-device): applied INSIDE the compiled train step to the raw
    # (possibly still uint8-staged) batch BEFORE from_record, with fresh
    # randomness each step from the step rng.  Zero host cost; never
    # applied at eval.  Signature: (batch_dict, rng) -> batch_dict.
    augment_fn: Optional[Callable[[Dict[str, Any], Any], Dict[str, Any]]] = None
    # Serving (decoder families; ``serve/engine.py`` and the continuous
    # scheduler read these, no model by name):
    # ``cache_rules(per_shard_pools) -> ShardingRules`` for the "cache"
    # collection the module's ``decode=True`` calls carry.
    cache_rules: Optional[Callable[..., ShardingRules]] = None
    # ``cache_geometry(paged) -> dict``: what a token costs in the paged
    # pool (values and bytes a token and layer, pool width, padding).
    cache_geometry: Optional[Callable[[Any], Dict[str, Any]]] = None
    # Scheduler features the family cannot serve yet -> the reason; the
    # scheduler refuses each at construction instead of falling back.
    serve_refusals: Dict[str, str] = dataclasses.field(default_factory=dict)
    # ``served_dtypes(params) -> tree of dtypes``, leaf for leaf over a
    # parameter tree (arrays or shapes): the compute type for a leaf that
    # every served program reads only through a cast to it, the leaf's own
    # for the rest.  The engine holds its weights so (``ServeEngine``,
    # "The served weights").  None: every leaf as the checkpoint has it.
    served_dtypes: Optional[Callable[[PyTree], PyTree]] = None


@dataclasses.dataclass(frozen=True)
class PagedKVConfig:
    """Geometry of the block-table (paged) KV cache — vLLM-style
    (Kwon et al., SOSP 2023; PAPERS.md).

    Instead of one dense ``(num_slots, max_total_len)`` K/V row per slot,
    K/V live in a ``(num_blocks, block_size, heads * head_dim)`` pool per
    layer — ``(layers, num_blocks, block_size, heads * head_dim)`` under
    the scanned stack — and each slot maps its logical positions to
    physical blocks through a host-managed ``(num_slots,
    max_blocks_per_slot)`` int32 block table passed into every decode
    call.  A request only pins the blocks its current length actually
    covers, so a 30-token request no longer reserves a full worst-case
    row.  Heads are merged into the minor dimension (head h owns columns
    ``[h * head_dim, (h + 1) * head_dim)``) so a block's rows are a
    multiple of the TPU's 128 lanes wide: a head size of 64 in the minor
    dimension would be padded to 128 and every program would re-lay the
    pool first.

    Physical block 0 is the TRASH block: never allocated to a request,
    it absorbs the garbage K/V that inactive decode rows write (their
    table rows are reset to all-zeros at retirement), so a freed-and-
    reused block can never be corrupted by a stale slot.

    ``kv_dtype`` selects the pool storage dtype: ``None`` stores the
    model's compute dtype (bit-identical to the dense cache), any dtype
    name (e.g. ``"bfloat16"``) casts on write, and ``"int8"`` stores
    symmetric per-token-quantized K/V plus f32 scale tables of shape
    ``(num_blocks, block_size)`` (one scale per written token position,
    shared across heads) that dequantize in the attention gather.

    TWO KINDS OF POOL (``window_ring > 0``): a family whose layers are
    partly window (sliding) attention keeps those layers' K/V in a second
    pool of ``window_blocks`` physical blocks in which every slot owns a
    RING of ``window_ring`` table entries whatever its length: position
    ``p`` lives in ring entry ``(p // block_size) % window_ring``, and a
    block that slid out of the window is overwritten in place.  The full
    layers keep the pool above.  Both tables travel as ONE int32 array of
    ``table_width(total_len)`` columns a slot: the full layers' entries
    first, the ring's ``window_ring`` entries last (``split_tables``).
    Block 0 of the window pool is its trash block.

    Frozen + hashable on purpose: the engine keys its jitted program cache
    by this config, and the model treats every field as compile-time
    static.
    """

    block_size: int = 16
    num_blocks: int = 64
    kv_dtype: Optional[str] = None  # None | "int8" | a jnp dtype name
    # Per-shard pools (fleet serving): partition the pool's block dimension
    # over the data axis — shard s owns blocks [s*per, (s+1)*per) with its
    # own trash block at s*per, and a slot's table only ever indexes its
    # shard.  1 keeps today's data-axis-replicated pool.
    data_shards: int = 1
    # The window layers' pool (see TWO KINDS OF POOL); 0 and 0 is none.
    window_blocks: int = 0
    window_ring: int = 0

    def __post_init__(self):
        if self.block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {self.block_size}")
        if self.num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is the reserved trash "
                f"block), got {self.num_blocks}")
        if self.data_shards < 1:
            raise ValueError(
                f"data_shards must be >= 1, got {self.data_shards}")
        if self.num_blocks % self.data_shards:
            raise ValueError(
                f"num_blocks {self.num_blocks} must divide evenly over "
                f"data_shards {self.data_shards} per-shard pools")
        if self.num_blocks // self.data_shards < 2:
            raise ValueError(
                f"num_blocks {self.num_blocks} leaves fewer than 2 blocks "
                f"per shard across data_shards {self.data_shards} (each "
                f"shard reserves its own trash block)")
        if self.kv_dtype is not None:
            jnp.dtype(self.kv_dtype)  # fail fast on typos
        if (self.window_ring > 0) != (self.window_blocks > 0):
            raise ValueError(
                f"window_blocks {self.window_blocks} and window_ring "
                f"{self.window_ring} go together (both 0: no window pool)")
        if self.window_ring < 0 or (
                self.window_ring and self.window_blocks < self.window_ring + 1):
            raise ValueError(
                f"window_blocks {self.window_blocks} must hold one slot's "
                f"ring of {self.window_ring} blocks plus the trash block")
        if self.window_ring and self.data_shards != 1:
            raise ValueError("per-shard pools are not built for a window pool")

    @property
    def quantized(self) -> bool:
        return self.kv_dtype == "int8"

    def storage_dtype(self, compute_dtype):
        if self.kv_dtype is None:
            return compute_dtype
        return jnp.dtype(self.kv_dtype)

    def blocks_for(self, tokens: int) -> int:
        """Physical blocks covering ``tokens`` logical positions."""
        return -(-max(0, tokens) // self.block_size)

    def prefix_blocks(self, prompt_len: int) -> int:
        """Most leading blocks of a ``prompt_len``-token prompt that
        prefix caching may map from cache: full blocks only, and never
        the whole prompt — prefill must compute at least the final
        position to emit the first sampled token, so a block-aligned
        prompt re-computes its last block into a private (copy-on-write)
        block instead of mapping it."""
        return max(0, int(prompt_len) - 1) // self.block_size

    def max_blocks_per_slot(self, total_len: int) -> int:
        return self.blocks_for(total_len)

    def table_width(self, total_len: int) -> int:
        """Columns of a slot's row in the block-table array: the full
        layers' entries and then the window ring's."""
        return self.max_blocks_per_slot(total_len) + self.window_ring

    def split_tables(self, block_tables):
        """The one table array -> (full layers' table, ring table)."""
        if not self.window_ring:
            return block_tables, None
        return (block_tables[:, :-self.window_ring],
                block_tables[:, -self.window_ring:])

    @property
    def window_capacity(self) -> int:
        """Positions a slot's ring holds."""
        return self.window_ring * self.block_size

    def blocks_for_megastep(self, prompt_len: int, generated: int,
                            steps: int, max_new_tokens: int) -> int:
        """Physical blocks a ``steps``-iteration fused decode (megastep)
        needs mapped BEFORE it launches.  The scan applies the cache
        ``steps`` times inside one program, so the scatter targets for
        every inner position must already resolve through the block
        table — there is no host boundary mid-scan to allocate at.
        Coverage clamps to the admission reservation
        (``prompt_len + max_new_tokens - 1``): a row whose horizon ends
        mid-megastep is alive-gated on device (its ``cache_index`` row
        freezes), so the positions past its horizon are only ever
        written as masked garbage — behind the frozen index, where the
        causal mask never admits them — and need no block of their own.
        """
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        covered = min(prompt_len + generated + steps - 1,
                      prompt_len + max_new_tokens - 1)
        return self.blocks_for(covered)

    def blocks_for_spec(self, prompt_len: int, generated: int,
                        draft_len: int, max_new_tokens: int) -> int:
        """Physical blocks a speculative verify launch needs mapped
        BEFORE it runs: the (1 + draft_len)-token forward scatters K/V
        for the last emitted token plus every draft position in ONE
        program, so all of them must already resolve through the block
        table — exactly the megastep precondition with
        ``steps = draft_len + 1``, including the clamp to the admission
        reservation (positions past the horizon are only ever written as
        masked garbage behind the rolled-back index)."""
        if draft_len < 0:
            raise ValueError(f"draft_len must be >= 0, got {draft_len}")
        return self.blocks_for_megastep(
            prompt_len, generated, draft_len + 1, max_new_tokens)

    @property
    def usable_blocks(self) -> int:
        """Blocks available to requests (pool minus the trash blocks)."""
        return self.num_blocks - self.data_shards

    @property
    def blocks_per_shard(self) -> int:
        return self.num_blocks // self.data_shards

    @property
    def usable_blocks_per_shard(self) -> int:
        """Blocks one data shard can hand to requests — the admission
        bound in per-shard mode (a shard cannot borrow a peer's blocks)."""
        return self.blocks_per_shard - 1

    def trash_block(self, shard: int = 0) -> int:
        return shard * self.blocks_per_shard



_REGISTRY = {
    "mnist": "distributed_tensorflow_tpu.models.mnist_cnn",
    "resnet50": "distributed_tensorflow_tpu.models.resnet",
    "bert": "distributed_tensorflow_tpu.models.bert",
    "gpt2": "distributed_tensorflow_tpu.models.gpt2",
    "glm4_moe_lite": "distributed_tensorflow_tpu.models.glm4_moe_lite",
    "mellum": "distributed_tensorflow_tpu.models.mellum",
    "glm_moe_dsa": "distributed_tensorflow_tpu.models.glm_moe_dsa",
    "solar_open2": "distributed_tensorflow_tpu.models.solar_open2",
    "dots3_note": "distributed_tensorflow_tpu.models.dots3_note",
    "wide_deep": "distributed_tensorflow_tpu.models.wide_deep",
}


def available_models() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_workload(name: str, **overrides) -> Workload:
    if name not in _REGISTRY:
        raise ValueError(f"Unknown model {name!r}; available: {available_models()}")
    # Lazy: ``obs`` imports the training loop, which must not import back
    # into a half-made ``models``.
    from distributed_tensorflow_tpu.obs.trace import default_tracer

    # The family's module is imported inside the span: what it pulls in is
    # part of what making a workload costs a process.
    with default_tracer().span("workload", cat="startup",
                               args={"model": name}):
        try:
            mod = importlib.import_module(_REGISTRY[name])
        except ModuleNotFoundError as e:
            raise NotImplementedError(
                f"Model family {name!r} is registered but its module "
                f"{_REGISTRY[name]} is not implemented yet"
            ) from e
        return mod.make_workload(**overrides)
