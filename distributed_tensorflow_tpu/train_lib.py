"""Unified training entrypoint logic — the "train.py runs unchanged" contract.

Behavioral model: the reference's per-model train.py scripts (SURVEY.md §3.5,
§4.1–4.3): they accept ``TF_CONFIG`` or ``--job_name/--task_index``, build a
distribution strategy, and loop.  Here one entrypoint serves all five
workloads; the launcher contract is preserved exactly (ps tasks park in
``server.join()``), and the distribution mechanics are TPU-native: mesh +
NamedSharding + one compiled step.
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import os
from typing import Any, Dict, Optional

import jax
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu import cluster as cluster_lib
from distributed_tensorflow_tpu import compile_cache
from distributed_tensorflow_tpu.checkpoint import CheckpointManager
from distributed_tensorflow_tpu.data import DevicePrefetchIterator
from distributed_tensorflow_tpu.models import Workload, available_models, get_workload
from distributed_tensorflow_tpu.obs.trace import default_tracer
from distributed_tensorflow_tpu.parallel.sharding import batch_sharding
from distributed_tensorflow_tpu.training import (
    BF16,
    FP32,
    CheckpointHook,
    EvalHook,
    LoggingHook,
    NanHook,
    ProfilerHook,
    TrainLoop,
    TrainState,
    carry_step_marks,
    make_eval_step,
    make_train_step,
)

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class TrainArgs:
    model: str = "mnist"
    arch: Optional[str] = None  # sub-architecture (wide_deep | dlrm)
    flash_attention: bool = False  # gpt2/bert: Pallas fused attention,
    # forward and backward (attention-prob dropout runs in-kernel — see
    # GPT2Config; speed not measured on the current chip)
    ring_chunk_size: int = 0  # gpt2/bert with --context>1: kv-chunk size
    # bounding per-ring-step attention memory (0 = whole blocks)
    pipe_schedule: str = "gpipe"  # gpt2 with --pipe>1: gpipe | 1f1b
    steps: int = 200
    batch_size: Optional[int] = None  # global; default from workload
    grad_accum_steps: Optional[int] = None
    learning_rate: Optional[float] = None
    precision: str = "bf16"
    # mesh axes (data=-1 absorbs the rest)
    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    pipe: int = 1
    context: int = 1
    expert: int = 1
    table_dtype: str = "f32"  # wide_deep: stored embedding-row dtype
    # launcher contract
    job_name: Optional[str] = None
    task_index: Optional[int] = None
    # io
    data_dir: Optional[str] = None  # {model}.rec or {model}-NNNNN-of-MMMMM
    # fileset in this dir -> native loader
    auto_shard_policy: str = "auto"  # fileset sharding: auto|file|data
    # (tf.data AutoShardPolicy roles; single-file datasets always stripe)
    data_service: Optional[str] = None  # host:port of a data.service server
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 1000
    max_to_keep: int = 3
    sync_checkpoint: bool = False  # block the step on checkpoint writes
    log_every: int = 50
    eval_every: int = 0  # 0 disables periodic evaluation
    eval_batches: int = 10
    profile_dir: Optional[str] = None
    tensorboard_dir: Optional[str] = None
    metrics_file: Optional[str] = None
    seed: int = 0
    # observability: 0 = no Prometheus scrape endpoint; >0 binds /metrics
    # on that port for the run's lifetime.
    metrics_port: int = 0
    # None = tracing off; a path enables the flight recorder and writes
    # Chrome trace-event JSON (Perfetto-loadable) there at teardown.
    trace_out: Optional[str] = None


def parse_args(argv=None) -> TrainArgs:
    p = argparse.ArgumentParser(description="TPU-native distributed training")
    p.add_argument("--model", choices=available_models(), default="mnist")
    p.add_argument("--arch", type=str, default=None,
                   help="sub-architecture for recsys models: wide_deep|dlrm")
    p.add_argument("--flash_attention", action="store_true",
                   help="gpt2/bert: use the Pallas fused-attention kernels "
                        "(forward AND backward — no (T,T) score buffer in "
                        "either pass; attention-prob dropout runs "
                        "in-kernel; speed not measured on the current "
                        "chip)")
    p.add_argument("--ring_chunk_size", type=int, default=0,
                   help="gpt2/bert with --context>1: consume ring-attention "
                        "kv blocks in chunks of this many keys (bounds "
                        "per-ring-step memory at long per-shard sequence "
                        "lengths; 0 = whole blocks)")
    p.add_argument("--pipe_schedule", choices=("gpipe", "1f1b"),
                   default="gpipe",
                   help="gpt2 with --pipe>1: GPipe (autodiff backward, "
                        "O(M) activation stash) or 1F1B (combined fwd/bwd "
                        "scan, depth-(2S-1) input ring stash + remat — "
                        "deep-pipe memory)")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch_size", type=int, default=None)
    p.add_argument("--grad_accum_steps", type=int, default=None)
    p.add_argument("--learning_rate", type=float, default=None)
    p.add_argument("--precision", choices=("bf16", "fp32"), default="bf16")
    for axis in ("data", "fsdp", "tensor", "pipe", "context", "expert"):
        p.add_argument(f"--{axis}", type=int,
                       default=-1 if axis == "data" else 1,
                       help=f"mesh size of the {axis!r} axis")
    p.add_argument("--table_dtype", choices=("f32", "bf16"), default="f32",
                   help="wide_deep: stored embedding-row dtype (bf16 halves "
                        "table param bytes; optimizer keeps an f32 master — "
                        "speed not measured on the current chip)")
    p.add_argument("--job_name", type=str, default=None,
                   help="TF1 launcher contract: ps|worker|chief|evaluator")
    p.add_argument("--task_index", type=int, default=None)
    p.add_argument("--data_dir", type=str, default=None,
                   help="directory holding {model}.rec or a "
                        "{model}-NNNNN-of-MMMMM.rec fileset; enables the "
                        "native C++ input loader (falls back to synthetic "
                        "data when unset)")
    p.add_argument("--auto_shard_policy", choices=("auto", "file", "data"),
                   default="auto",
                   help="multi-file dataset sharding across hosts: whole "
                        "files (file), record striping (data), or file-"
                        "when-enough-files (auto) — the tf.data "
                        "AutoShardPolicy roles")
    p.add_argument("--data_service", type=str, default=None,
                   help="host:port of an out-of-process input server "
                        "(data.service — the tf.data-service role); "
                        "mutually exclusive with --data_dir")
    p.add_argument("--checkpoint_dir", type=str, default=None)
    p.add_argument("--checkpoint_every", type=int, default=1000)
    p.add_argument("--max_to_keep", type=int, default=3,
                   help="retained checkpoints (tf.train.CheckpointManager "
                        "max_to_keep, checkpoint_management.py:519)")
    p.add_argument("--sync_checkpoint", action="store_true",
                   help="block the training step on checkpoint writes "
                        "(default: async orbax saves overlap training)")
    p.add_argument("--log_every", type=int, default=50)
    p.add_argument("--eval_every", type=int, default=0,
                   help="run evaluation every N steps (0 = off)")
    p.add_argument("--eval_batches", type=int, default=10)
    p.add_argument("--profile_dir", type=str, default=None)
    p.add_argument("--tensorboard_dir", type=str, default=None)
    p.add_argument("--metrics_file", type=str, default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--metrics_port", type=int, default=0,
                   help="serve a Prometheus /metrics scrape endpoint "
                        "(step-time histogram, flush counters) on this "
                        "port for the run's lifetime (0 = off)")
    p.add_argument("--trace_out", type=str, default=None,
                   help="write Chrome trace-event JSON (checkpoint "
                        "save/restore spans; load in Perfetto) here at "
                        "teardown (unset = tracing off)")
    ns = p.parse_args(argv)
    return TrainArgs(**vars(ns))


def _wrap_from_record(workload: Workload, fn, *, train: bool = False):
    """Apply the workload's device-side input transforms to the batch
    before the loss — inside the compiled step: per-step augmentation
    (``augment_fn``, TRAIN ONLY, on the raw possibly-uint8 batch) then the
    staging inverse (``from_record``, no-op for unstaged batches)."""
    aug = workload.augment_fn if train else None
    fr = workload.from_record
    if fn is None or (aug is None and fr is None):
        return fn

    def pre(b, rng):
        if aug is not None:
            b = aug(b, rng)
        return fr(b) if fr is not None else b

    if workload.stateful:
        return lambda p, ms, b, rng: fn(p, ms, pre(b, rng), rng)
    return lambda p, b, rng: fn(p, pre(b, rng), rng)


def build_step(
    workload: Workload,
    mesh,
    *,
    precision=BF16,
    grad_accum_steps: int = 1,
    learning_rate: Optional[float] = None,
    total_steps: int = 1000,
    seed: int = 0,
):
    """The sharded step, built without touching device memory.

    Returns ``(init, abstract_state, state_shardings, train_step,
    batch_shardings)``: ``init()`` materializes the sharded TrainState,
    ``train_step`` is the jitted step.  Both can be lowered from shapes
    alone (``init.lower()``, ``train_step.lower(abstract_state, ...)``) —
    how the tests ask what program a full-width mesh gets without running
    it."""
    tracer = default_tracer()
    with tracer.span("build_step", cat="startup") as phase:
        lr = learning_rate if learning_rate is not None else workload.learning_rate
        schedule = optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=lr,
            warmup_steps=min(workload.warmup_steps, max(1, total_steps // 10)),
            decay_steps=max(2, total_steps),
        )
        if workload.make_optimizer is not None:
            tx = workload.make_optimizer(schedule)
        else:
            tx = optax.adamw(schedule, weight_decay=1e-4)

        rng = jax.random.key(seed)

        def init_fn():
            init_input = (
                workload.init_batch if workload.init_key is None
                else workload.init_batch[workload.init_key]
            )
            variables = dict(workload.module.init(rng, init_input))
            params = variables.pop("params")
            return TrainState.create(
                apply_fn=workload.module.apply, params=params, tx=tx,
                model_state=variables,
            )

        with tracer.span("abstract_state", cat="startup"):
            abstract_state = jax.eval_shape(init_fn)
        # One rule table shards params AND optimizer moments: regex paths match
        # both "params/.../kernel" and "opt_state/.../mu/.../kernel".
        with tracer.span("shardings", cat="startup"):
            state_shardings = workload.rules.shardings_for(mesh, abstract_state)
        jitted_init = jax.jit(init_fn, out_shardings=state_shardings)

        def init():
            with tracer.span("state_init", cat="startup"):
                return jitted_init()

        # Lowerable from shapes alone, as the jitted function is.
        init.lower = jitted_init.lower

        # shard_map paths (ring attention over `context`, GPipe over `pipe`)
        # need static per-shard shapes: every microbatch must divide the batch
        # axes exactly.  Plain GSPMD paths tolerate uneven sharding, so only
        # enforce where the cryptic shard_map divisibility error would hit.
        if mesh.shape.get("context", 1) > 1 or mesh.shape.get("pipe", 1) > 1:
            batch_par = mesh.shape.get("data", 1) * mesh.shape.get("fsdp", 1)
            micro = workload.batch_size // max(1, grad_accum_steps)
            if micro % max(1, batch_par):
                raise ValueError(
                    f"microbatch {micro} (= batch {workload.batch_size} / "
                    f"grad_accum {grad_accum_steps}) does not divide the batch "
                    f"axes data*fsdp={batch_par}; raise --batch_size or lower "
                    "--grad_accum_steps"
                )
        with tracer.span("make_step", cat="startup"):
            raw_step = make_train_step(
                _wrap_from_record(workload, workload.loss_fn, train=True),
                grad_accum_steps=grad_accum_steps,
                precision=precision,
                clip_grad_norm=workload.clip_grad_norm,
                jit=False,
                stateful=workload.stateful,
                # Async-loop contract: the step folds state.step into a constant
                # base key on device, so the loop never splits keys host-side.
                in_step_rng=True,
                # Where the mesh has a `data` axis to defer over, each replica sums
                # its own microbatches' gradients and the step reduces them once.
                mesh=mesh,
                state_shardings=state_shardings,
                batch_rows=workload.batch_size,
            )
        # Where the step sums gradients over ``data``: the build-time fact the
        # span carries (``train_step.grad_reduce`` is the same word).
        phase.set(grad_reduce=raw_step.grad_reduce,
                  data=mesh.shape.get("data", 1), accum=grad_accum_steps)
        bsh = batch_sharding(mesh)
        batch_shardings = {k: bsh for k in workload.init_batch}
        train_step = carry_step_marks(raw_step, jax.jit(
            raw_step,
            in_shardings=(state_shardings, batch_shardings, NamedSharding(mesh, P())),
            out_shardings=(state_shardings, None),
            donate_argnums=(0,),
        ))
        return init, abstract_state, state_shardings, train_step, batch_shardings


def build_state_and_step(workload: Workload, mesh, **kwargs):
    """Initialize a sharded TrainState + sharded compiled train step
    (``build_step`` plus the one call that allocates)."""
    init, _, state_shardings, train_step, batch_shardings = build_step(
        workload, mesh, **kwargs)
    return init(), state_shardings, train_step, batch_shardings


# Mesh axes each workload can actually honor.  Axes a workload cannot honor
# are hard errors, not silent replication (a --pipe the model ignores would
# have N-1 of N devices doing duplicate work).
_MODEL_AXES = {
    "gpt2": {"pipe", "context"},
    "bert": {"context"},
    "wide_deep": {"expert"},  # multi-table embeddings shard over expert
}


def validate_mesh_axes(args: TrainArgs) -> None:
    """Reject mesh axes the selected workload does not implement."""
    supported = _MODEL_AXES.get(args.model, set())
    for axis, why in (
        ("pipe", "GPipe pipeline stages"),
        ("context", "ring attention / sequence parallelism"),
        ("expert", "embedding-table sharding"),
    ):
        if getattr(args, axis) > 1 and axis not in supported:
            raise ValueError(
                f"--{axis}={getattr(args, axis)} ({why}) is not wired into "
                f"--model={args.model}; it would silently replicate over "
                f"the {axis!r} axis. Models supporting it: "
                f"{sorted(m for m, a in _MODEL_AXES.items() if axis in a)}"
            )


def run(args: TrainArgs) -> Dict[str, Any]:
    """Full entrypoint. Returns final host metrics (for tests/benchmarks)."""
    # force=True: a library imported earlier may have configured root
    # handlers already, which would silently swallow basicConfig and
    # therefore all INFO logs.
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s: %(message)s",
        force=True,
    )

    # 1. Launcher contract: resolve cluster role.
    resolver = cluster_lib.resolve(args.job_name, args.task_index)
    server = cluster_lib.Server.from_resolver(resolver)
    if not resolver.is_compute_task():
        if resolver.task_type == "evaluator" and args.checkpoint_dir:
            # The reference's evaluator job continuously evaluates new
            # checkpoints (TF estimator train-and-evaluate contract).
            result = run_evaluator(args)
            server.shutdown()
            return result
        logger.info(
            "task %s:%s is a %s task: parameters are mesh-sharded on TPU; "
            "parking in join() for launcher compatibility",
            resolver.task_type, resolver.task_id, resolver.task_type,
        )
        server.join()
        return {}

    # 2. Mesh over the global device set.
    validate_mesh_axes(args)
    mesh = cluster_lib.build_mesh(
        cluster_lib.MeshConfig(
            data=args.data, fsdp=args.fsdp, tensor=args.tensor,
            pipe=args.pipe, context=args.context, expert=args.expert,
        )
    )
    logger.info("mesh: %s over %d devices", dict(mesh.shape), mesh.size)

    # 3. Workload.  The mesh is passed so mesh-aware models (sharded
    # embeddings) can bind their exchange axis; factories ignore it otherwise.
    overrides = {"mesh": mesh}
    if args.batch_size:
        overrides["batch_size"] = args.batch_size
    if args.grad_accum_steps:
        # The factory must see the REAL accum count: gpt2's dense-attention
        # memory guard sizes the microbatch from it.
        overrides["grad_accum_steps"] = args.grad_accum_steps
    if args.arch:
        if args.model != "wide_deep":
            raise ValueError(
                f"--arch only applies to --model=wide_deep, got "
                f"--model={args.model} --arch={args.arch}"
            )
        overrides["arch"] = args.arch
    if args.table_dtype != "f32":
        if args.model != "wide_deep":
            raise ValueError("--table_dtype applies to --model=wide_deep "
                             "(the embedding-table workloads)")
        overrides["table_dtype"] = args.table_dtype
    if args.flash_attention:
        if args.model not in ("gpt2", "bert"):
            raise ValueError("--flash_attention applies to gpt2/bert "
                             "(the attention workloads)")
        overrides["use_flash_attention"] = True
    if args.ring_chunk_size:
        if args.model not in ("gpt2", "bert"):
            raise ValueError("--ring_chunk_size applies to gpt2/bert "
                             "(the ring-attention workloads)")
        if args.context <= 1:
            raise ValueError("--ring_chunk_size requires --context>1 "
                             "(ring attention is the context-axis path)")
        overrides["ring_chunk_size"] = args.ring_chunk_size
    if args.pipe_schedule != "gpipe":
        if args.model != "gpt2":
            raise ValueError("--pipe_schedule applies to --model=gpt2 "
                             "(the pipelined workload)")
        if args.pipe <= 1:
            raise ValueError("--pipe_schedule=1f1b requires --pipe>1")
        overrides["pipe_schedule"] = args.pipe_schedule
    workload = get_workload(args.model, **overrides)
    grad_accum = args.grad_accum_steps or workload.grad_accum_steps
    precision = BF16 if args.precision == "bf16" else FP32

    if args.trace_out:
        default_tracer().enable()
    state, state_shardings, train_step, batch_shardings = build_state_and_step(
        workload,
        mesh,
        precision=precision,
        grad_accum_steps=grad_accum,
        learning_rate=args.learning_rate,
        total_steps=args.steps,
        seed=args.seed,
    )

    # Cross-host consistency guard before the first collective (SURVEY §6.2).
    cluster_lib.assert_same_program("train_state", jax.eval_shape(lambda s: s, state))

    # 4. Input pipeline: per-host slice -> global sharded arrays -> prefetch.
    # The stream layout comes from the batch sharding's REAL process
    # partition, not from process_count: on a context/model-parallel-only
    # mesh the batch dim is replicated, so every host must feed the SAME
    # full-batch stream (per-process decorrelated halves would assemble an
    # inconsistent "replicated" array silently).
    bsh = batch_shardings[workload.example_key]
    from distributed_tensorflow_tpu.data.pipeline import (
        host_batch_layout,
        set_stream_shard_override,
    )

    host_bs, stream_shards, stream_index = host_batch_layout(
        bsh, workload.batch_size)
    if (stream_shards, stream_index) != (jax.process_count(),
                                         jax.process_index()):
        logger.info(
            "batch layout: %d rows/host as stream shard %d/%d (batch dim "
            "not process-partitioned 1:1)", host_bs, stream_index,
            stream_shards)
    set_stream_shard_override(stream_shards, stream_index)
    if args.data_service and args.data_dir:
        raise ValueError("--data_service and --data_dir are mutually "
                         "exclusive (the service owns the record file)")
    if args.data_service:
        from distributed_tensorflow_tpu.data.service import (
            data_service_data_fn,
        )

        if stream_shards != jax.process_count() and jax.process_count() > 1:
            raise ValueError(
                "--data_service splits ONE stream across consumers, which "
                "cannot express a replicated batch dim (context/model-"
                "parallel-only mesh); use --data_dir or synthetic input")
        logger.info("out-of-process input service: %s", args.data_service)
        host_iter = data_service_data_fn(args.data_service, workload)(host_bs)
    elif args.data_dir:
        from distributed_tensorflow_tpu.data.records import (
            record_data_fn,
            record_paths,
        )

        from distributed_tensorflow_tpu.native import reader_name

        paths = record_paths(args.data_dir, args.model)
        logger.info("record loader (reader=%s): %d file(s), %s%s",
                    reader_name(), len(paths), paths[0],
                    "" if len(paths) == 1 else " ..")
        host_iter = record_data_fn(
            paths, workload, seed=args.seed,
            shard_index=stream_index, shard_count=stream_shards,
            policy=args.auto_shard_policy,
        )(host_bs)
    else:
        host_iter = workload.data_fn(host_bs)
    data_iter = DevicePrefetchIterator(host_iter, bsh, prefetch=2)

    # 5. Hooks.
    from distributed_tensorflow_tpu.obs import PrefetchMonitorHook
    from distributed_tensorflow_tpu.obs.startup import StartupReportHook

    hooks = [
        LoggingHook(every_steps=args.log_every),
        NanHook(),
        PrefetchMonitorHook(data_iter, every_steps=max(args.log_every, 1)),
        # One ``startup`` line when the first loss has landed.
        StartupReportHook(),
    ]
    if jax.process_count() > 1:
        # Peer-liveness fail-fast (MWMS check-health equivalent, SURVEY
        # §6.3): a dead peer raises at the next step boundary instead of
        # hanging this worker in a collective forever.
        from distributed_tensorflow_tpu.ft import HealthCheckHook

        interval = float(os.environ.get("DTT_HEALTH_INTERVAL_S", "30"))
        hooks.append(HealthCheckHook(
            interval_s=interval,
            timeout_s=min(20.0, max(1.0, interval * 0.75)),
            # Skewed startup/compile beyond 10 min is legitimate for big
            # models — the grace must be raisable without a code change.
            startup_grace_s=float(
                os.environ.get("DTT_HEALTH_STARTUP_GRACE_S", "600")),
        ))
    manager = None
    if args.checkpoint_dir:
        manager = CheckpointManager(
            args.checkpoint_dir, max_to_keep=args.max_to_keep,
            save_interval_steps=args.checkpoint_every,
            async_save=not args.sync_checkpoint,
        )
        state = manager.restore_or_init(state)
        hooks.append(CheckpointHook(manager, every_steps=args.checkpoint_every))
        # Fault tolerance (SURVEY §6.3): preemption signal → coordinated
        # checkpoint + stop; restart resumes via restore_or_init above.
        from distributed_tensorflow_tpu.ft import PreemptionCheckpointHook

        hooks.append(PreemptionCheckpointHook(manager))
    if args.profile_dir:
        hooks.append(ProfilerHook(args.profile_dir))
    if args.tensorboard_dir:
        from distributed_tensorflow_tpu.obs import TensorBoardHook

        hooks.append(TensorBoardHook(args.tensorboard_dir,
                                     every_steps=args.log_every))
    if args.metrics_file:
        from distributed_tensorflow_tpu.obs import MetricsFileWriter

        hooks.append(MetricsFileWriter(args.metrics_file))
    if args.eval_every > 0:
        eval_step = make_eval_step(
            _wrap_from_record(workload, workload.eval_loss_fn or workload.loss_fn),
            precision=precision, stateful=workload.stateful,
        )
        eval_iter = make_eval_data(workload, batch_shardings)
        writers = [h for h in hooks if callable(getattr(h, "write", None))]
        hooks.append(EvalHook(
            eval_step, eval_iter, every_steps=args.eval_every,
            num_batches=args.eval_batches, writers=writers,
        ))

    # 6. Loop.
    metrics_server = None
    if args.metrics_port:
        from distributed_tensorflow_tpu.obs import MetricsServer

        metrics_server = MetricsServer(port=args.metrics_port)
    loop = TrainLoop(
        train_step,
        state,
        data_iter,
        hooks=hooks,
        examples_per_step=workload.batch_size,
        metrics_every=min(10, args.log_every),
        rng=jax.random.key(args.seed + 1),
    )
    start_step = int(jax.device_get(state.step))
    remaining = max(0, args.steps - start_step)
    try:
        final_state = loop.run(remaining)
    finally:
        # Teardown runs on errors too: the data-service client must send
        # its quit opcode (else the trainer socket and the server's
        # per-connection serve thread persist until process exit), and the
        # prefetch thread / checkpoint manager / server must not leak
        # across repeated in-process runs (as in tests).
        data_iter.close()
        if callable(getattr(host_iter, "close", None)):
            host_iter.close()
        set_stream_shard_override(None)
        if manager is not None:
            manager.close()
        if args.trace_out:
            from distributed_tensorflow_tpu.obs import write_chrome_trace

            write_chrome_trace(args.trace_out)
        if metrics_server is not None:
            metrics_server.close()
        server.shutdown()

    result = {
        "final_step": int(jax.device_get(final_state.step)),
        **loop.last_logged_metrics,
        "device": cluster_lib.device_summary(),
    }
    logger.info("done: %s", result)
    return result


def make_eval_data(workload, batch_shardings):
    """Eval input stream: the workload's held-out split (eval_data_fn),
    sharded like the train batches.  Falls back to the training stream with
    a warning — eval-on-train cannot measure generalization."""
    from distributed_tensorflow_tpu.data.pipeline import (
        host_batch_layout,
        make_global_batches,
    )

    fn = workload.eval_data_fn
    if fn is None:
        logger.warning(
            "workload %r has no eval_data_fn; evaluating on the TRAINING "
            "stream", workload.name,
        )
        fn = workload.data_fn
    bsh = batch_shardings[workload.example_key]
    host_bs, _, _ = host_batch_layout(bsh, workload.batch_size)
    return make_global_batches(fn(host_bs), bsh)


def run_evaluator(args: TrainArgs) -> Dict[str, Any]:
    """Sidecar evaluator: poll the checkpoint dir, evaluate each new step.

    The reference runs this as the ``evaluator`` job of TF_CONFIG (estimator
    train_and_evaluate); here it is a read-only process — it restores into
    its own mesh and never joins the training collectives.
    """
    import time as _time

    validate_mesh_axes(args)
    mesh = cluster_lib.build_mesh(cluster_lib.MeshConfig(
        data=args.data, fsdp=args.fsdp, tensor=args.tensor,
        pipe=args.pipe, context=args.context, expert=args.expert,
    ))
    overrides: Dict[str, Any] = {"mesh": mesh}
    if args.batch_size:
        overrides["batch_size"] = args.batch_size
    workload = get_workload(args.model, **overrides)
    precision = BF16 if args.precision == "bf16" else FP32
    state, state_shardings, _, batch_shardings = build_state_and_step(
        workload, mesh, precision=precision, total_steps=max(args.steps, 2),
    )
    manager = CheckpointManager(args.checkpoint_dir, save_interval_steps=1)
    eval_step = make_eval_step(
        _wrap_from_record(workload, workload.eval_loss_fn or workload.loss_fn),
        precision=precision, stateful=workload.stateful,
    )
    eval_iter = make_eval_data(workload, batch_shardings)
    rng = jax.random.key(args.seed + 2)

    last_seen = -1
    results: Dict[str, Any] = {}
    idle_timeout_s = float(os.environ.get("DTT_EVAL_IDLE_TIMEOUT_S", "600"))
    last_progress = _time.monotonic()
    while True:
        step = manager.latest_step()
        if _time.monotonic() - last_progress > idle_timeout_s:
            logger.warning(
                "evaluator: no new checkpoint in %.0fs (last step %d); "
                "assuming the trainer is gone and exiting",
                idle_timeout_s, last_seen,
            )
            break
        if step is not None and step > last_seen:
            last_progress = _time.monotonic()
            state = manager.restore(step, template=state)
            sums: Dict[str, float] = {}
            for _ in range(args.eval_batches):
                rng, sub = jax.random.split(rng)
                m = eval_step(state, next(eval_iter), sub)
                for k, v in m.items():
                    sums[k] = sums.get(k, 0.0) + float(jax.device_get(v))
            results = {f"eval_{k}": v / args.eval_batches
                       for k, v in sums.items()}
            logger.info("evaluator @ step %d: %s", step, results)
            last_seen = step
        if last_seen >= args.steps:
            break
        _time.sleep(2.0)
    manager.close()
    return {"final_step": last_seen, **results}


def main(argv=None):
    args = parse_args(argv)
    compile_cache.configure()
    result = run(args)
    if result:
        print(result)
    return result


if __name__ == "__main__":
    main()
