"""Monitored training loop with hooks.

Behavioral model: TF1's ``MonitoredTrainingSession`` + session-run hooks
($TF/python/training/monitored_session.py:428;
basic_session_run_hooks.py — ``LoggingTensorHook``:169, ``StepCounterHook``
:674, ``CheckpointSaverHook``:524, ``NanTensorHook``:761 — SURVEY.md §6.5)
and TF2 Keras ``Model.fit``'s callback loop.  The loop is deliberately thin:
the heavy lifting happens inside the compiled step; hooks observe at step
boundaries on the host.

The hot path is fully asynchronous (the async-loop contract):

- **RNG**: with an in-step-RNG train step (``make_train_step(...,
  in_step_rng=True)``, the ``train_lib`` default) the loop passes the SAME
  base key every step and the compiled program folds ``state.step`` into it
  — ``run_one_step`` is pure dispatch, no host-side ``random.split``.
  Steps built without the flag keep the legacy per-step host split.
- **Metrics**: never pulled synchronously.  At step N (a ``metrics_every``
  boundary) the loop starts ``copy_to_host_async()`` on the metrics pytree;
  the transfer is consumed — one batched ``device_get`` over already-landed
  buffers — at step N+``metrics_every``.  Hooks therefore observe step-N
  metrics one interval late; ``loop.last_metrics_step`` names the step the
  delivered values belong to, and ``Hook.on_metrics`` receives it directly.
  ``run`` flushes the final pending interval before hooks ``end``.
"""

from __future__ import annotations

import logging
import math
import sys
import time
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax
import numpy as np

from distributed_tensorflow_tpu.training.metrics import RunningMean, ThroughputMeter
from distributed_tensorflow_tpu.training.train_state import TrainState

logger = logging.getLogger(__name__)
PyTree = Any


class Hook:
    """Step-boundary observer (SessionRunHook equivalent).

    ``after_step`` fires every step; its ``metrics`` argument is non-None
    only when a deferred fetch landed this step, and then holds the metrics
    of ``loop.last_metrics_step`` (one ``metrics_every`` interval behind —
    the async-loop contract).  ``on_metrics`` is the value-delivery channel:
    it receives the TRUE step the metrics belong to, including the final
    flush that ``run``/``flush_metrics`` performs after the last step (when
    ``after_step`` will not fire again).
    """

    def begin(self, loop: "TrainLoop") -> None:  # noqa: D401
        pass

    def after_step(self, loop: "TrainLoop", step: int,
                   metrics: Optional[Dict[str, float]]) -> None:
        pass

    def on_metrics(self, loop: "TrainLoop", metrics_step: int,
                   metrics: Dict[str, float]) -> None:
        pass

    def end(self, loop: "TrainLoop", step: int) -> None:
        pass


class LoggingHook(Hook):
    """LoggingTensorHook + StepCounterHook in one."""

    def __init__(self, every_steps: int = 100):
        self.every_steps = every_steps
        self._mean = RunningMean()
        # Constructed here (not in begin) so a hook driven through
        # ``after_step`` without a prior ``begin`` (compat surfaces that
        # drive ``run_one_step`` directly) never hits an AttributeError;
        # ``begin`` re-arms it with the loop's real examples_per_step.
        self._meter = ThroughputMeter(0)

    def begin(self, loop):
        self._meter = ThroughputMeter(loop.examples_per_step)

    def on_metrics(self, loop, metrics_step, metrics):
        self._mean.update(metrics)

    def after_step(self, loop, step, metrics):
        self._meter.update()
        if step % self.every_steps == 0 and step > 0:
            m = {**self._mean.report_and_reset(), **self._meter.report()}
            msg = ", ".join(f"{k}={v:.4g}" for k, v in sorted(m.items()))
            logger.info("step %d: %s", step, msg)
            loop.last_logged_metrics = m


class NanHook(Hook):
    """Stop (or raise) on non-finite loss (NanTensorHook equivalent).

    Deferred-metrics semantics: the check runs when the values LAND (one
    ``metrics_every`` interval after the step that produced them), so up to
    ``metrics_every`` further steps may have executed — they are discarded
    on restart anyway, and the error names the step that actually NaN'd.
    """

    def __init__(self, fail_on_nan: bool = True):
        self.fail_on_nan = fail_on_nan

    def on_metrics(self, loop, metrics_step, metrics):
        loss = metrics.get("loss")
        if loss is not None and not math.isfinite(loss):
            if self.fail_on_nan:
                raise FloatingPointError(
                    f"Non-finite loss at step {metrics_step}: {loss}")
            logger.error("Non-finite loss at step %d; requesting stop",
                         metrics_step)
            loop.request_stop()


class CheckpointHook(Hook):
    """CheckpointSaverHook equivalent over the orbax manager.

    Unaffected by the deferred-metrics lag: it saves ``loop.state`` on the
    true step cadence (the state at step N IS step N's state; only metric
    *values* arrive an interval late).
    """

    def __init__(self, manager, every_steps: int = 1000):
        self.manager = manager
        self.every_steps = every_steps

    def after_step(self, loop, step, metrics):
        if step > 0 and step % self.every_steps == 0:
            self.manager.save(step, loop.state)

    def end(self, loop, step):
        self.manager.save(step, loop.state, force=True)
        self.manager.wait_until_finished()


class ProfilerHook(Hook):
    """jax.profiler trace over a step window (tf.profiler equivalent,
    SURVEY.md §6.1)."""

    def __init__(self, log_dir: str, start_step: int = 10, num_steps: int = 5):
        self.log_dir = log_dir
        self.start_step = start_step
        self.stop_step = start_step + num_steps
        self._active = False

    def after_step(self, loop, step, metrics):
        if step == self.start_step and not self._active:
            jax.profiler.start_trace(self.log_dir)
            self._active = True
        elif step >= self.stop_step and self._active:
            jax.profiler.stop_trace()
            self._active = False

    def end(self, loop, step):
        if self._active:
            jax.profiler.stop_trace()
            self._active = False


class EvalHook(Hook):
    """Periodic in-training evaluation (the reference's evaluator pattern,
    inlined: TF1 ran a separate evaluator job re-reading checkpoints; with a
    compiled eval step the cheaper TPU-native form is to evaluate in-loop at
    an interval).  Averages metrics over ``num_batches`` eval batches.

    Deferred-metrics semantics: evaluation triggers on the true step cadence
    and evaluates the CURRENT ``loop.state`` — the training-metric lag does
    not shift what is evaluated.  The eval pull itself is blocking by
    design (it already sits outside the hot path).
    """

    def __init__(self, eval_step: Callable, data_iter: Iterable,
                 *, every_steps: int, num_batches: int = 10,
                 rng: Optional[jax.Array] = None,
                 writers: Optional[List["Hook"]] = None):
        self.eval_step = eval_step
        self.data_iter = iter(data_iter)
        self.every_steps = max(1, every_steps)
        self.num_batches = num_batches
        self.rng = rng if rng is not None else jax.random.key(17)
        self.last_eval_metrics: Dict[str, float] = {}
        # Metric-writer hooks (TensorBoard/JSONL) to push eval points into —
        # they only see per-step metrics otherwise.
        self.writers = writers or []

    def _evaluate(self, loop, step):
        sums: Dict[str, float] = {}
        for _ in range(self.num_batches):
            batch = next(self.data_iter)
            self.rng, sub = jax.random.split(self.rng)
            m = self.eval_step(loop.state, batch, sub)
            for k, v in m.items():
                sums[k] = sums.get(k, 0.0) + float(np.asarray(jax.device_get(v)))
        self.last_eval_metrics = {
            f"eval_{k}": v / self.num_batches for k, v in sums.items()
        }
        loop.last_logged_metrics.update(self.last_eval_metrics)
        msg = ", ".join(f"{k}={v:.4g}"
                        for k, v in sorted(self.last_eval_metrics.items()))
        logger.info("eval @ step %d: %s", step, msg)
        for w in self.writers:
            write = getattr(w, "write", None)
            if callable(write):
                write(step, self.last_eval_metrics)

    def after_step(self, loop, step, metrics):
        if step % self.every_steps == 0 and step > 0:
            self._evaluate(loop, step)

    def end(self, loop, step):
        if step > 0 and step % self.every_steps != 0:
            self._evaluate(loop, step)


class TrainLoop:
    """Drives (state, batch) -> state for a fixed number of steps.

    The hot path never blocks on the device (module docstring: the
    async-loop contract).  Metric transfers START every ``metrics_every``
    steps and are CONSUMED one interval later; hooks see step-N values at
    step N+``metrics_every`` with ``last_metrics_step == N``.

    ``fold_rng=None`` (default) auto-detects: train steps built with
    ``in_step_rng=True`` carry a marker attribute and receive the constant
    base ``rng`` every call (the step folds ``state.step`` in on device);
    unmarked steps get the legacy host-side per-step ``random.split``.
    Pass ``fold_rng=True``/``False`` to override the detection.
    """

    def __init__(
        self,
        train_step: Callable,
        state: TrainState,
        data_iter: Iterable[PyTree],
        *,
        hooks: Optional[List[Hook]] = None,
        examples_per_step: int = 0,
        metrics_every: int = 10,
        rng: Optional[jax.Array] = None,
        fold_rng: Optional[bool] = None,
    ):
        self.train_step = train_step
        self.state = state
        self.data_iter = iter(data_iter)
        self.hooks = hooks or []
        self.examples_per_step = examples_per_step
        self.metrics_every = max(1, metrics_every)
        self.rng = rng if rng is not None else jax.random.key(0)
        self.fold_rng = fold_rng
        self.last_logged_metrics: Dict[str, float] = {}
        self.last_step_metrics: Optional[Dict[str, float]] = None
        # Step the last delivered metrics belong to (== delivery step minus
        # metrics_every under the deferred contract); None before the first
        # delivery.
        self.last_metrics_step: Optional[int] = None
        # (step, device metrics pytree) whose host copy is in flight.
        self._pending_metrics: Optional[tuple] = None
        self._stop = False
        # ``dtt/startup/first_step``: from the dispatch of the first step
        # this loop ever runs (its trace, lowering and compile or cache
        # read fall inside) until the first loss is fetched.
        self._first_step_at: Optional[float] = None
        self._first_step_recorded = False
        # Lazy import: obs.__init__ pulls in the hook modules, which import
        # THIS module — importing obs.metrics at the top here would re-enter
        # the partially-initialized obs package whenever training.loop is
        # imported first.
        from distributed_tensorflow_tpu.obs import metrics as obs_metrics
        from distributed_tensorflow_tpu.obs import trace as obs_trace

        self._tracer = obs_trace.default_tracer()
        reg = obs_metrics.default_registry()
        self._obs_step_time = reg.histogram(
            "dtt_train_step_seconds",
            "Host-side dispatch duration of one train step")
        self._obs_steps = reg.counter(
            "dtt_train_steps_total", "Train steps dispatched")
        self._obs_flushes = reg.counter(
            "dtt_train_metrics_flush_total",
            "Deferred-metrics fetches consumed on the host")

    def request_stop(self) -> None:
        self._stop = True

    @property
    def stopped(self) -> bool:
        """Whether a stop was requested (hook, NaN, or data exhaustion) —
        further ``run`` calls will make no progress."""
        return self._stop

    # -- deferred metrics --------------------------------------------------

    def _start_metrics_fetch(self, step: int, metrics: PyTree) -> None:
        """Begin the device→host copy without blocking the dispatch loop."""
        for leaf in jax.tree.leaves(metrics):
            start = getattr(leaf, "copy_to_host_async", None)
            if callable(start):
                start()
        self._pending_metrics = (step, metrics)

    def _consume_pending_metrics(self):
        """(metrics_step, host dict) of the in-flight fetch, or (None, None).

        One batched ``device_get`` over the whole pytree; the async copies
        started an interval ago have normally landed, so this does not
        drain the device pipeline.
        """
        if self._pending_metrics is None:
            return None, None
        step, tree = self._pending_metrics
        self._pending_metrics = None
        with self._tracer.span("metrics_fetch", cat="train"):
            host_tree = jax.device_get(tree)
        if not self._first_step_recorded:
            self._first_step_recorded = True
            self._tracer.add_span(
                "first_step", cat="startup", start=self._first_step_at,
                end=time.perf_counter(), args={"metrics_step": step})
        host = {k: float(np.asarray(v)) for k, v in host_tree.items()}
        self._obs_flushes.inc()
        return step, host

    def _deliver(self, metrics_step: int, host: Dict[str, float]) -> None:
        self.last_metrics_step = metrics_step
        self.last_step_metrics = host
        with self._tracer.span("hooks", cat="train",
                               args={"call": "on_metrics"}):
            for h in self.hooks:
                h.on_metrics(self, metrics_step, host)

    def flush_metrics(self) -> Optional[Dict[str, float]]:
        """Consume the in-flight metrics fetch immediately (end of a run
        segment / session close — ``after_step`` will not fire again for
        it).  Delivers through ``Hook.on_metrics`` and returns the dict."""
        mstep, host = self._consume_pending_metrics()
        if host is None:
            return None
        self._deliver(mstep, host)
        self.last_logged_metrics.update(host)
        return host

    # -- stepping ----------------------------------------------------------

    def _step_rng(self, fn) -> jax.Array:
        fold = self.fold_rng
        if fold is None:
            fold = getattr(fn, "_dtt_in_step_rng", False)
        if fold:
            # In-step RNG: the compiled program folds state.step into the
            # base key; the SAME array is passed every call (pure dispatch).
            return self.rng
        self.rng, step_rng = jax.random.split(self.rng)  # legacy compat
        return step_rng

    def run_one_step(self, completed_steps: int, train_step=None) -> int:
        """One step: feed a batch, run the compiled step, drive hooks.

        Returns the new completed-step count.  Shared by ``run`` and the
        TF1 ``compat.v1.MonitoredTrainingSession.run`` so both loop bodies
        are the same code.  An exhausted data iterator requests stop (the
        TF1 OutOfRangeError-ends-the-session contract) and leaves the count
        unchanged.  No host↔device synchronization happens here: RNG is
        folded in-step (or split host-side on the legacy path), and metric
        fetches are started asynchronously and consumed an interval later.
        """
        with self._tracer.span("step", cat="train"):
            return self._one_step(completed_steps, train_step)

    def _one_step(self, completed_steps: int, train_step) -> int:
        fn = train_step if train_step is not None else self.train_step
        try:
            with self._tracer.span("next_batch", cat="train"):
                batch = next(self.data_iter)
        except StopIteration:
            self.request_stop()
            self.last_step_metrics = None
            return completed_steps
        t0 = time.perf_counter()
        if self._first_step_at is None:
            self._first_step_at = t0
        rng = self._step_rng(fn)
        with self._tracer.span("dispatch", cat="train"):
            self.state, metrics = fn(self.state, batch, rng)
        self._obs_step_time.observe(time.perf_counter() - t0)
        self._obs_steps.inc()
        completed_steps += 1
        host_metrics = None
        if completed_steps % self.metrics_every == 0:
            mstep, host_metrics = self._consume_pending_metrics()
            self._start_metrics_fetch(completed_steps, metrics)
            if host_metrics is not None:
                self._deliver(mstep, host_metrics)
        self.last_step_metrics = host_metrics
        with self._tracer.span("hooks", cat="train",
                               args={"call": "after_step"}):
            for h in self.hooks:
                h.after_step(self, completed_steps, host_metrics)
        return completed_steps

    def run(self, num_steps: int) -> TrainState:
        # Parent of the steps' spans: what a run spends outside them (the
        # hooks' begin and end, the state's step fetched, the last flush).
        with self._tracer.span("run", cat="train"):
            return self._run(num_steps)

    def _run(self, num_steps: int) -> TrainState:
        for h in self.hooks:
            h.begin(self)
        start = int(jax.device_get(self.state.step))
        completed = start  # last step the state actually reflects
        try:
            for _ in range(num_steps):
                if self._stop:
                    break
                completed = self.run_one_step(completed)
        finally:
            try:
                # Only flush on the clean path: re-delivering on an already-
                # propagating error would mask it (e.g. NanHook re-raising
                # from inside finally).
                if sys.exc_info()[0] is None:
                    self.flush_metrics()
            finally:
                for h in self.hooks:
                    h.end(self, completed)
        return self.state
