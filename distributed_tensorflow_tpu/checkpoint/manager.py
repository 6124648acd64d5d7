"""Checkpoint save/restore over orbax (async, sharded, resumable).

Behavioral model: SURVEY.md §4.5 — TF's object-based ``tf.train.Checkpoint``
($TF/python/checkpoint/checkpoint.py:2061) + ``CheckpointManager``
(checkpoint_management.py:519: max_to_keep, keep_every, latest_checkpoint)
and TF1's Saver-driven ``CheckpointSaverHook``.  TPU-native answer (SURVEY.md
§6.4): orbax-checkpoint over tensorstore — every host writes its own shards
(no chief-writes-all bottleneck, unlike the reference's MWMS where non-chief
workers write to throwaway temp dirs), restore re-shards to the current mesh
automatically.
"""

from __future__ import annotations

import logging
from typing import Any, Optional

import jax
import orbax.checkpoint as ocp
from etils import epath

from distributed_tensorflow_tpu.obs import metrics as obs_metrics
from distributed_tensorflow_tpu.obs.trace import default_tracer, now

logger = logging.getLogger(__name__)
PyTree = Any


def _ckpt_instruments(registry=None):
    r = registry or obs_metrics.default_registry()
    return {
        "save": r.histogram(
            "dtt_checkpoint_save_seconds",
            "save() host-side duration (async: dispatch, not completion)"),
        "restore": r.histogram(
            "dtt_checkpoint_restore_seconds", "restore() duration"),
    }


class CheckpointManager:
    """max_to_keep / save_interval / latest-restore, tf.train-shaped."""

    def __init__(
        self,
        directory: str,
        *,
        max_to_keep: int = 5,
        save_interval_steps: int = 1,
        async_save: bool = True,
        item_names: tuple = ("state",),
    ):
        self._directory = epath.Path(directory)
        self._options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            save_interval_steps=save_interval_steps,
            enable_async_checkpointing=async_save,
        )
        self._mngr = ocp.CheckpointManager(self._directory, options=self._options)
        self._obs = _ckpt_instruments()
        self._tracer = default_tracer()

    # -- tf.train.CheckpointManager-compatible surface -----------------------
    @property
    def directory(self) -> str:
        return str(self._directory)

    def latest_step(self) -> Optional[int]:
        return self._mngr.latest_step()

    @property
    def latest_checkpoint(self) -> Optional[str]:
        step = self.latest_step()
        return None if step is None else str(self._directory / str(step))

    def all_steps(self):
        return self._mngr.all_steps()

    def poll(self) -> Optional[int]:
        """Cheap watcher surface: re-scan the directory and return the
        newest step — no restore, no template.  Orbax caches its step
        listing, so ``latest_step()`` alone never notices checkpoints
        written by ANOTHER process (or another manager instance); the
        fleet's checkpoint watcher needs the fresh ``reload()`` scan.
        Returns None when no checkpoint exists yet or after ``close()``.
        """
        if self._mngr is None:
            return None
        reload_fn = getattr(self._mngr, "reload", None)
        if callable(reload_fn):  # older orbax has no reload(); scan below
            reload_fn()
        return self._mngr.latest_step()

    def save(self, step: int, state: PyTree, *, force: bool = False) -> bool:
        """Save ``state`` at ``step`` (async by default; returns whether a
        save was started, honoring save_interval_steps like TF's manager)."""
        if step in self._mngr.all_steps():
            return False
        t0 = now()
        with self._tracer.span("checkpoint_save", cat="checkpoint",
                               args={"step": int(step)}):
            saved = self._mngr.save(
                step, args=ocp.args.StandardSave(state), force=force
            )
        self._obs["save"].observe(now() - t0)
        if saved:
            logger.info("checkpoint save started at step %d -> %s", step,
                        self.directory)
        return saved

    def restore(self, step: Optional[int] = None, *, template: PyTree) -> PyTree:
        """Restore at ``step`` (default latest) re-sharded like ``template``.

        ``template`` may be a concrete state (its shardings are reused) or a
        pytree of ShapeDtypeStruct with shardings.
        """
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"No checkpoint found in {self.directory}")
        abstract = jax.tree.map(_abstractify, template)
        t0 = now()
        with self._tracer.span("checkpoint_restore", cat="checkpoint",
                               args={"step": int(step)}):
            out = self._mngr.restore(
                step, args=ocp.args.StandardRestore(abstract))
        self._obs["restore"].observe(now() - t0)
        return out

    def restore_or_init(self, state: PyTree) -> PyTree:
        """Resume-if-present: the auto-resume contract of fault tolerance
        (SURVEY.md §6.3 — PreemptionCheckpointHandler restart-resume)."""
        if self.latest_step() is None:
            return state
        restored = self.restore(template=state)
        logger.info("resumed from checkpoint step %s", self.latest_step())
        return restored

    def restore_params(self, step: Optional[int] = None):
        """Inference-only restore: ``(params, model_state)`` as host arrays.

        Reads the raw saved tree (no template), so the caller never has to
        reconstruct the optimizer that wrote the checkpoint and no
        optimizer moments are sharded onto devices — the serve engine's
        restore path.  ``model_state`` is ``{}`` for stateless models.
        """
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"No checkpoint found in {self.directory}")
        t0 = now()
        with self._tracer.span("checkpoint_restore", cat="checkpoint",
                               args={"step": int(step)}):
            tree = self._mngr.restore(step, args=ocp.args.StandardRestore())
        self._obs["restore"].observe(now() - t0)
        # A TrainState round-trips through StandardSave as a dict of its
        # pytree fields; tolerate an attr-style container too.
        if isinstance(tree, dict):
            return tree["params"], dict(tree.get("model_state") or {})
        return tree.params, dict(getattr(tree, "model_state", None) or {})

    # -- teardown surface ----------------------------------------------------
    # Async orbax saves run on background threads that can outlive short
    # serve/bench processes; ``close`` is the one call every owner (train
    # teardown, serve engine, evaluator) makes — it drains outstanding
    # saves first and is safe to call twice.

    def wait_until_finished(self) -> None:
        if self._mngr is not None:
            self._mngr.wait_until_finished()

    def close(self) -> None:
        if self._mngr is None:
            return
        self.wait_until_finished()
        self._mngr.close()
        self._mngr = None

    @property
    def closed(self) -> bool:
        return self._mngr is None

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False


def _abstractify(x):
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
    return x
