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


_REGISTRY = {
    "mnist": "distributed_tensorflow_tpu.models.mnist_cnn",
    "resnet50": "distributed_tensorflow_tpu.models.resnet",
    "bert": "distributed_tensorflow_tpu.models.bert",
    "gpt2": "distributed_tensorflow_tpu.models.gpt2",
    "glm4_moe_lite": "distributed_tensorflow_tpu.models.glm4_moe_lite",
    "wide_deep": "distributed_tensorflow_tpu.models.wide_deep",
}


def available_models() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_workload(name: str, **overrides) -> Workload:
    if name not in _REGISTRY:
        raise ValueError(f"Unknown model {name!r}; available: {available_models()}")
    try:
        mod = importlib.import_module(_REGISTRY[name])
    except ModuleNotFoundError as e:
        raise NotImplementedError(
            f"Model family {name!r} is registered but its module "
            f"{_REGISTRY[name]} is not implemented yet"
        ) from e
    return mod.make_workload(**overrides)
