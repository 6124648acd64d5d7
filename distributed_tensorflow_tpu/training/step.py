"""The compiled training step: forward/backward/update as one XLA program.

Behavioral model: the reference's per-step path (SURVEY.md §4.1): per-replica
forward/backward, gradient allreduce via CollectiveAllReduce, optimizer
apply.  TPU-native, the *entire* step — including the gradient mean across
data-parallel shards and the optimizer update — is one jitted program.
Without accumulation XLA inserts the AllReduce from the shardings (no
explicit collective) and overlaps it with backward compute.

Gradient accumulation (the reference's GPT-2-medium answer to memory,
BASELINE.json config 5) is a ``lax.scan`` over microbatches — static shapes,
one compilation, accumulators in f32.  Left to the shardings, the scan's
accumulator is replicated over ``data``, so every microbatch's gradient is
all-reduced before it is added: ``grad_accum_steps`` reductions of every
layer's gradient a step.  The sum over microbatches and the sum over
replicas commute, so where the mesh has a ``data`` axis to defer over
(``grad_reduce_site``) each replica scans its own rows inside a
``shard_map`` that is manual over ``data`` alone, adds to a local f32
accumulator with no collective, and the step reduces the accumulator over
``data`` once, after the scan.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.training.train_state import Precision, BF16, TrainState

PyTree = Any
# loss_fn(params, batch, rng) -> (loss, aux_metrics)
LossFn = Callable[[PyTree, PyTree, jax.Array], Tuple[jax.Array, Dict[str, jax.Array]]]
# stateful variant (models with mutable collections, e.g. BatchNorm):
# loss_fn(params, model_state, batch, rng) -> (loss, aux, new_model_state)
StatefulLossFn = Callable[
    [PyTree, PyTree, PyTree, jax.Array],
    Tuple[jax.Array, Dict[str, jax.Array], PyTree],
]


def mark_in_step_rng(fn, flag: bool):
    """Tag a step fn (raw or jitted) so ``TrainLoop`` knows whether its rng
    argument is a per-step key (legacy) or a constant base key that the
    compiled program folds ``state.step`` into."""
    try:
        fn._dtt_in_step_rng = flag
    except AttributeError:  # exotic callables that reject attributes
        pass
    return fn


def carry_step_marks(src, dst):
    """What a step says of itself (``_dtt_in_step_rng``, ``grad_reduce``),
    carried from ``src`` onto ``dst``, its re-jitted twin."""
    mark_in_step_rng(dst, getattr(src, "_dtt_in_step_rng", False))
    if hasattr(src, "grad_reduce"):
        dst.grad_reduce = src.grad_reduce
    return dst


# The axis whose replicas each hold whole rows of the batch and a whole copy
# of the parameters: the one a gradient reduction can be deferred over.
_DATA = "data"
# Axes a model runs a ``shard_map`` of its own over (GPipe stages, ring
# attention, the embedding tables' exchange).  Those maps name ``data`` in
# their specs, so they cannot nest under a region that is manual over it.
_MODEL_MAP_AXES = ("pipe", "context", "expert")


def _names(spec: P):
    for entry in spec:
        yield from (entry if isinstance(entry, tuple) else (entry,))


def grad_reduce_site(
    mesh: Optional[Mesh],
    grad_accum_steps: int,
    *,
    batch_rows: Optional[int] = None,
    stateful: bool = False,
    state_shardings: PyTree = None,
) -> str:
    """Where a step built for ``mesh`` sums its gradients over ``data``.

    - ``"none"``: no mesh, or a ``data`` axis of one: nothing to reduce.
    - ``"after_scan"``: each data replica accumulates its own microbatches'
      gradients and the step reduces the accumulator once.
    - ``"in_scan"``: left to GSPMD, which reduces each gradient where it is
      made (inside the microbatch scan, when there is one).  That is: no
      accumulation to defer over; a batch of ``batch_rows`` rows that does
      not give every replica ``grad_accum_steps`` whole microbatches (GSPMD
      pads an uneven split, a manual region cannot); a stateful workload
      (batch statistics are global under GSPMD and would become
      per-replica inside the map); a mesh on which the model runs a
      ``shard_map`` of its own (``_MODEL_MAP_AXES``); or state that is
      itself split over ``data`` (wide-and-deep's tables), whose gradient
      has no replica sum.
    """
    if mesh is None or mesh.shape.get(_DATA, 1) == 1:
        return "none"
    defer = (
        grad_accum_steps > 1
        and not (batch_rows or 0) % (mesh.shape[_DATA] * grad_accum_steps)
        and not stateful
        and all(mesh.shape.get(a, 1) == 1 for a in _MODEL_MAP_AXES)
        and not any(_DATA in _names(getattr(sh, "spec", ()))
                    for sh in jax.tree.leaves(state_shardings))
    )
    return "after_scan" if defer else "in_scan"


def make_train_step(
    loss_fn: LossFn,
    *,
    grad_accum_steps: int = 1,
    precision: Precision = BF16,
    clip_grad_norm: Optional[float] = None,
    donate: bool = True,
    jit: bool = True,
    stateful: bool = False,
    in_step_rng: bool = False,
    mesh: Optional[Mesh] = None,
    state_shardings: PyTree = None,
    batch_rows: Optional[int] = None,
) -> Callable[[TrainState, PyTree, jax.Array], Tuple[TrainState, Dict[str, jax.Array]]]:
    """Build the (optionally jitted) train step.

    With ``grad_accum_steps > 1`` the batch's leading dim must be
    ``grad_accum_steps * microbatch``; it is reshaped and scanned.
    Pass ``jit=False`` to get the raw step fn for re-jitting with explicit
    shardings (``shard_train_step``) or for embedding in a larger program.
    ``stateful=True`` switches to the ``StatefulLossFn`` signature and
    threads ``state.model_state`` (e.g. batch_stats) through the step.

    ``in_step_rng=True`` makes the rng argument a *base* key: the compiled
    program derives the per-step key as ``fold_in(rng, state.step)``, so
    the caller passes the SAME key every step — no host-side ``split`` in
    the hot loop (the async-loop contract; ``TrainLoop`` auto-detects this
    via a marker attribute).  The default keeps the legacy per-step-key
    signature for existing callers.

    ``mesh`` (with ``state_shardings``, where the state is placed by rule,
    and ``batch_rows``, the rows of the global batch, where they may not
    divide) is the mesh the step will be jitted for.  Where
    ``grad_reduce_site`` says ``"after_scan"`` the accumulation runs per
    data replica: each scans its own ``rows / data`` rows as
    ``grad_accum_steps`` microbatches (the same rows a step, grouped into
    microbatches by replica), folds its ``data`` index into the rng so that
    dropout masks differ between replicas, and the f32 accumulator, the
    loss and the aux metrics are reduced over ``data`` once, after the
    scan.  The loss is then the mean
    of per-replica microbatch means where it was a mean of global
    microbatch means: identical for a loss over equal counts (GPT-2's
    tokens), and for a mean over a varying count (BERT's masked positions)
    the same kind of contract accumulation already makes.  The returned
    step's ``grad_reduce`` attribute is ``grad_reduce_site``'s word.
    """
    where = grad_reduce_site(
        mesh, grad_accum_steps, batch_rows=batch_rows, stateful=stateful,
        state_shardings=state_shardings)

    def compute_grads(params, model_state, batch, rng):
        compute_params = precision.cast_for_compute(params)

        def scalar_loss(p, b):
            if stateful:
                loss, aux, new_ms = loss_fn(p, model_state, b, rng)
                return loss.astype(jnp.float32), (aux, new_ms)
            loss, aux = loss_fn(p, b, rng)
            return loss.astype(jnp.float32), (aux, model_state)

        (loss, (aux, new_ms)), grads = jax.value_and_grad(scalar_loss, has_aux=True)(
            compute_params, batch
        )
        # Master-dtype gradients for the f32 accumulator/optimizer.
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return loss, aux, grads, new_ms

    def accumulate(params, model_state, batch, rng):
        """Sums over ``batch``'s ``grad_accum_steps`` microbatches: the f32
        gradients and the loss; the last model state; aux, stacked."""
        micro = jax.tree.map(
            lambda x: x.reshape((grad_accum_steps, -1) + x.shape[1:]), batch
        )

        def body(carry, mb):
            acc, loss_acc, ms = carry
            mb_rng = jax.random.fold_in(rng, loss_acc[1].astype(jnp.int32))
            loss, aux, grads, new_ms = compute_grads(params, ms, mb, mb_rng)
            acc = jax.tree.map(jnp.add, acc, grads)
            return (acc, (loss_acc[0] + loss, loss_acc[1] + 1), new_ms), aux

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, (loss_sum, _), new_ms), aux = jax.lax.scan(
            body,
            (zero, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
             model_state),
            micro,
        )
        return grads, loss_sum, new_ms, aux

    def replica_accumulate(params, model_state, batch, rng):
        """``accumulate`` over this data replica's rows, then the one
        reduction over ``data``.  The model state comes back as it went in
        (a stateful workload never gets here)."""
        rng = jax.random.fold_in(rng, jax.lax.axis_index(_DATA))
        grads, loss_sum, _, aux = accumulate(params, model_state, batch, rng)
        grads, loss_sum = jax.lax.psum((grads, loss_sum), _DATA)
        return grads, loss_sum, model_state, jax.lax.pmean(aux, _DATA)

    def step(state: TrainState, batch: PyTree, rng: jax.Array):
        if in_step_rng:
            # rng is a constant base key; derive this step's key on device.
            rng = jax.random.fold_in(rng, state.step.astype(jnp.uint32))
        if grad_accum_steps == 1:
            loss, aux, grads, new_ms = compute_grads(
                state.params, state.model_state, batch, rng
            )
        else:
            if where == "after_scan":
                # Manual over ``data`` only: ``tensor``/``fsdp`` stay with
                # GSPMD, and the flash kernel's own map nests inside.
                sums = jax.shard_map(
                    replica_accumulate,
                    mesh=mesh,
                    in_specs=(P(), P(), P(_DATA), P()),
                    out_specs=P(),
                    axis_names={_DATA},
                    check_vma=False,
                )
                n_micro = grad_accum_steps * mesh.shape[_DATA]
            else:
                sums, n_micro = accumulate, grad_accum_steps
            grads, loss_sum, new_ms, aux = sums(
                state.params, state.model_state, batch, rng)
            grads = jax.tree.map(lambda g: g / n_micro, grads)
            loss = loss_sum / n_micro
            aux = jax.tree.map(lambda x: x.mean(axis=0), aux)

        metrics = {"loss": loss, **aux}
        if clip_grad_norm is not None:
            gnorm = optax.global_norm(grads)
            scale = jnp.minimum(1.0, clip_grad_norm / (gnorm + 1e-6))
            grads = jax.tree.map(lambda g: g * scale, grads)
            metrics["grad_norm"] = gnorm
        new_state = state.apply_gradients(grads, new_model_state=new_ms)
        return new_state, metrics

    mark_in_step_rng(step, in_step_rng)
    step.grad_reduce = where
    if not jit:
        return step
    donate_argnums = (0,) if donate else ()
    return carry_step_marks(step, jax.jit(step, donate_argnums=donate_argnums))


def make_eval_step(
    loss_fn: LossFn, *, precision: Precision = BF16, stateful: bool = False
) -> Callable[[TrainState, PyTree, jax.Array], Dict[str, jax.Array]]:
    def step(state: TrainState, batch: PyTree, rng: jax.Array):
        params = precision.cast_for_compute(state.params)
        if stateful:
            loss, aux, _ = loss_fn(params, state.model_state, batch, rng)
        else:
            loss, aux = loss_fn(params, batch, rng)
        return {"loss": loss.astype(jnp.float32), **aux}

    return jax.jit(step)


def shard_train_step(
    train_step: Callable,
    mesh: Mesh,
    state_shardings: PyTree,
    batch_sharding: NamedSharding,
):
    """Re-jit a train step with explicit in/out shardings.

    This is where the MultiWorkerMirroredStrategy contract is enforced
    TPU-natively: state shardings say where parameters live (replicated for
    pure DP, partitioned for fsdp/tensor), the batch sharding splits input
    over data axes, and XLA derives every collective from that.

    The in-step-RNG marker (``make_train_step(in_step_rng=True)``) and the
    step's ``grad_reduce`` word are propagated onto the re-jitted step, so
    ``TrainLoop`` keeps detecting the one and a reader can ask the other.
    """
    jitted = jax.jit(
        train_step.__wrapped__ if hasattr(train_step, "__wrapped__") else train_step,
        in_shardings=(state_shardings, batch_sharding, NamedSharding(mesh, P())),
        out_shardings=(state_shardings, NamedSharding(mesh, P())),
        donate_argnums=(0,),
    )
    return carry_step_marks(train_step, jitted)
