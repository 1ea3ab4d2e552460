"""Checkpoint / export of JAX pytrees.

Reference behavior: TFoS delegates checkpointing entirely to TensorFlow
(``SURVEY.md §5`` — ``model_dir`` on HDFS, TF1 ``MonitoredTrainingSession``
auto-restore, export via ``compat.py::export_saved_model``).  The TPU rebuild
keeps the same delegation shape — the framework persists nothing of its own —
but the artifact is an Orbax checkpoint of a JAX pytree behind the same
``model_dir``/``export_dir`` parameters.

Two layers:

- :func:`save_pytree` / :func:`load_pytree` — one-shot export/import (used by
  ``compat.export_saved_model`` and ``TFModel``).
- :class:`CheckpointManager` — step-numbered training checkpoints with
  retention and (optionally) async save, for restart-from-checkpoint recovery
  (the reference's failure model: ``spark.task.maxFailures=1`` + restore).
"""

from __future__ import annotations

import logging
import os
from typing import Any

logger = logging.getLogger(__name__)


def _checkpointer():
    import orbax.checkpoint as ocp

    return ocp.PyTreeCheckpointer()


def _canonical(path: str) -> str:
    """Absolutize local paths; leave URI-style paths (gs://, hdfs://) alone —
    orbax/tensorstore handles those natively and abspath would mangle them."""
    if "://" in path:
        return path
    return os.path.abspath(path)


def save_pytree(state: Any, path: str) -> str:
    """Save a pytree (params/opt-state/step, arbitrary nesting) to ``path``."""
    from tensorflowonspark_tpu import obs

    path = _canonical(path)
    if "://" not in path:
        os.makedirs(os.path.dirname(path), exist_ok=True)
    with obs.span("ckpt.save", path=path):
        _checkpointer().save(path, state, force=True)
    logger.info("saved checkpoint to %s", path)
    return path


def saved_tree(path: str) -> Any:
    """The tree a checkpoint written by :func:`save_pytree` holds, a leaf's
    metadata in each array's place: what was saved, and no array is read."""
    # orbax API drift: PyTreeCheckpointer.metadata returns the metadata tree
    # directly (≤0.7-era), or an object carrying it under
    # .item_metadata.tree (newer composite handlers)
    meta_tree = _checkpointer().metadata(_canonical(path))
    item_md = getattr(meta_tree, "item_metadata", None)
    if item_md is not None:
        meta_tree = getattr(item_md, "tree", item_md)
    return meta_tree


def load_pytree(path: str, target: Any | None = None) -> Any:
    """Restore a pytree saved by :func:`save_pytree`.

    Without ``target``, returns nested dicts of **numpy** arrays — restoring
    as device arrays would need the sharding recorded at save time, which
    references the *writer's* topology and fails on any other (a CPU-mesh
    export served on a TPU chip, the cross-platform serving path).  Numpy is
    topology-agnostic; consumers ``device_put`` with their own shardings.
    With ``target`` (a pytree of like-shaped arrays), restores into that
    structure/placement.
    """
    import orbax.checkpoint as ocp

    from tensorflowonspark_tpu import obs

    path = _canonical(path)
    with obs.span("ckpt.restore", path=path, targeted=target is not None):
        if target is None:
            import jax
            import numpy as np

            restore_args = jax.tree.map(
                lambda _: ocp.RestoreArgs(restore_type=np.ndarray),
                saved_tree(path))
            return _checkpointer().restore(
                path, args=ocp.args.PyTreeRestore(restore_args=restore_args))

        # carry the TARGET's shardings into the restore: without them orbax
        # falls back to the sharding file recorded by the WRITER, which
        # references the writer's topology and is wrong (or fails) on any
        # other — e.g. restarting on a differently-shaped mesh
        restore_args = ocp.checkpoint_utils.construct_restore_args(target)
        return _checkpointer().restore(
            path, args=ocp.args.PyTreeRestore(item=target,
                                              restore_args=restore_args))


class CheckpointManager:
    """Step-numbered checkpoints with retention, for mid-training recovery."""

    def __init__(self, directory: str, max_to_keep: int = 3, async_save: bool = False):
        import orbax.checkpoint as ocp

        self._directory = _canonical(directory)
        if "://" not in self._directory:
            os.makedirs(self._directory, exist_ok=True)
        options = ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep, enable_async_checkpointing=async_save
        )
        self._mgr = ocp.CheckpointManager(self._directory, options=options)

    @property
    def directory(self) -> str:
        return self._directory

    def save(self, step: int, state: Any) -> None:
        import orbax.checkpoint as ocp

        from tensorflowonspark_tpu import obs

        with obs.span("ckpt.save", path=self._directory, step=step):
            self._mgr.save(step, args=ocp.args.StandardSave(state))

    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def restore(self, step: int | None = None, target: Any | None = None) -> Any:
        """Restore checkpoint ``step`` (default: newest committed).

        With ``target`` the restore is resharded to the *target's*
        topology (``StandardRestore`` carries the target's shardings, not
        the writer's recorded ones) — the property the elastic-regroup
        path depends on: survivors rebuild their mesh over a smaller
        device set and restore the old world's checkpoint straight into
        it."""
        import orbax.checkpoint as ocp

        if step is None:
            step = self._mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self._directory}")
        if target is None:
            return self._mgr.restore(step)
        return self._mgr.restore(step, args=ocp.args.StandardRestore(target))

    def saved_tree(self, step: int) -> Any:
        """The tree checkpoint ``step`` holds, a leaf's metadata in each
        array's place (:func:`saved_tree`)."""
        meta = self._mgr.item_metadata(step)
        return getattr(meta, "tree", meta)

    def wait_until_finished(self) -> None:
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.close()
