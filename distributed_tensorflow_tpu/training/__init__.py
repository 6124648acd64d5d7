"""Training loop, compiled step, state, metrics (SURVEY.md §2 L6, §4.1)."""

from distributed_tensorflow_tpu.training.loop import (
    CheckpointHook,
    EvalHook,
    Hook,
    LoggingHook,
    NanHook,
    ProfilerHook,
    TrainLoop,
)
from distributed_tensorflow_tpu.training.metrics import RunningMean, ThroughputMeter
from distributed_tensorflow_tpu.training.step import (
    carry_step_marks,
    grad_reduce_site,
    make_eval_step,
    make_train_step,
    mark_in_step_rng,
    shard_train_step,
)
from distributed_tensorflow_tpu.training.train_state import (
    BF16,
    FP32,
    Precision,
    TrainState,
)

__all__ = [
    "BF16",
    "FP32",
    "CheckpointHook",
    "EvalHook",
    "Hook",
    "LoggingHook",
    "NanHook",
    "Precision",
    "ProfilerHook",
    "RunningMean",
    "ThroughputMeter",
    "TrainLoop",
    "TrainState",
    "carry_step_marks",
    "grad_reduce_site",
    "make_eval_step",
    "make_train_step",
    "mark_in_step_rng",
    "shard_train_step",
]
