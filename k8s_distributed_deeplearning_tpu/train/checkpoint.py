"""Checkpoint save/restore — the ``MonitoredTrainingSession`` semantics, done right.

Reference behavior: rank-0-only ``checkpoint_dir='./checkpoints'`` with
implicit periodic save *and restore-on-start* handled by
``MonitoredTrainingSession`` (``tensorflow_mnist.py:157-167``); the Keras
variant adds per-epoch ``ModelCheckpoint`` + final ``model.save``
(``tensorflow_mnist_gpu.py:160-163,190-191``). Known reference flaw: saves go
to pod-local disk with no volume mounted (``tensorflow-mnist.yaml:43-53``) —
checkpoints die with the pod.

Here: Orbax-backed, multi-host-correct (Orbax coordinates across processes;
in the single-controller case the primary-process gate reproduces the
``hvd.rank() == 0`` discipline, ``:159``), directory is config so the rendered
manifest can point it at a PVC/GCS mount, and restore-on-start is explicit.
"""
from __future__ import annotations

import os
import sys
from typing import Any

import jax
import orbax.checkpoint as ocp

from k8s_distributed_deeplearning_tpu.utils import ckpt as ckpt_paths

PyTree = Any


class Checkpointer:
    """Thin synchronous wrapper over an Orbax ``CheckpointManager``.

    ``keep_best_metric`` switches retention to best-by-metric — the
    ``ModelCheckpoint(..., save_best_only=True)`` semantics of the reference's
    Keras variant (``tensorflow_mnist_gpu.py:160-163``): saves carry an eval
    metric via ``save(..., metrics={...})``, and ``max_to_keep`` retains the
    *best* checkpoints by that metric instead of the newest. The NEWEST
    checkpoint is additionally always preserved (LatestN + BestN
    preservation policies), so metric-less periodic saves keep crash-resume
    recent even after ``max_to_keep`` fills with best-by-metric checkpoints
    — without the extra slot, a crash after a long eval-free stretch would
    silently replay from the last *best* step.
    """

    def __init__(self, directory: str, max_to_keep: int = 3,
                 keep_best_metric: str | None = None,
                 best_mode: str = "max", async_save: bool = False,
                 portable_transforms=None, metrics=None):
        """``portable_transforms`` is an optional ``(to_portable,
        from_portable)`` pair canonicalizing the ON-DISK layout: ``save``
        writes ``to_portable(state)`` and the restore paths return
        ``from_portable(restored)``. Trainers whose in-memory state uses a
        schedule-specific layout (the interleaved pipeline's chunk-arranged
        ``[V, P, L/PV, ...]`` blocks — ``PipelineTrainer
        .portable_transforms``) pass their reshapes here so checkpoints
        stay interchangeable across schedules and with the non-pipelined
        trainers (cross-topology restore, the elastic-resize contract).

        *metrics* is an optional :class:`~utils.metrics.MetricsLogger`;
        integrity failures found by the restore chain emit through it as
        ``ckpt_quarantined`` events (and always print to stderr — a
        quarantine must never be silent)."""
        self.directory = os.path.abspath(directory)
        self.keep_best_metric = keep_best_metric
        self.async_save = async_save
        self.metrics = metrics
        self.quarantined: list[tuple[int, str]] = []   # (step, reason)
        # Steps saved but not yet manifested (async saves commit later;
        # the manifest is written once the step dir exists on disk).
        self._pending_manifests: set[int] = set()
        self._to_portable, self._from_portable = portable_transforms or (
            None, None)
        if keep_best_metric is not None:
            # orbax doesn't re-export preservation policies at top level;
            # `orbax.checkpoint.checkpoint_managers` is the most public
            # path that carries them (not `_src`, but version-sensitive —
            # verified on orbax-checkpoint 0.11.x, and the LatestN+BestN
            # semantics are pinned by tests/test_checkpoint.py, which is
            # the tripwire if an upgrade moves or reshapes this API).
            from orbax.checkpoint.checkpoint_managers import (
                preservation_policy as pp)
            metric_fn = lambda m: float(m[keep_best_metric])
            options = ocp.CheckpointManagerOptions(
                preservation_policy=pp.AnyPreservationPolicy(policies=[
                    pp.LatestN(n=1),        # crash-resume recency slot
                    pp.BestN(get_metric_fn=metric_fn,
                             # BestN keeps the tail of an ascending sort;
                             # reverse flips it for best_mode="min".
                             reverse=best_mode == "min",
                             n=max_to_keep,
                             keep_checkpoints_without_metrics=False),
                ]),
                # best_fn/best_mode still drive best_step().
                best_fn=metric_fn, best_mode=best_mode, create=True)
        else:
            options = ocp.CheckpointManagerOptions(max_to_keep=max_to_keep,
                                                   create=True)
        self._mgr = ocp.CheckpointManager(
            self.directory, options=options,
            # Explicit handler so a fresh manager can read item_metadata of an
            # existing checkpoint (restore_params) without a prior save.
            item_handlers=ocp.StandardCheckpointHandler(),
        )

    def save(self, step: int, state: PyTree, force: bool = False,
             metrics: dict | None = None) -> bool:
        """Save *state* at *step*. With ``async_save`` the device arrays are
        snapshotted synchronously but serialization/IO runs on Orbax's
        background thread — the train loop keeps stepping while the previous
        checkpoint writes (Orbax itself serializes overlapping saves).
        Synchronous mode (default) blocks until the write is durable."""
        if self._to_portable is not None:
            state = self._to_portable(state)
        saved = self._mgr.save(step, args=ocp.args.StandardSave(state),
                               force=force, metrics=metrics)
        if saved:
            self._pending_manifests.add(step)
        if not self.async_save:
            self._mgr.wait_until_finished()
        self._flush_manifests()
        return saved

    def wait(self) -> None:
        """Block until outstanding async saves are durable (no-op when
        synchronous)."""
        self._mgr.wait_until_finished()
        self._flush_manifests()

    def _flush_manifests(self) -> None:
        """Write integrity manifests for every save whose step dir has
        committed (sync saves: immediately; async saves: whenever the
        background write finishes — next save()/wait()/close() picks them
        up), then GC manifests orphaned by Orbax retention."""
        for step in sorted(self._pending_manifests):
            if os.path.isdir(os.path.join(self.directory, str(step))):
                ckpt_paths.write_manifest(self.directory, step)
                self._pending_manifests.discard(step)
        ckpt_paths.gc_manifests(self.directory)

    def best_step(self) -> int | None:
        """Step of the best checkpoint by the tracked metric (None when not
        in best-tracking mode or nothing metric-carrying was saved)."""
        if self.keep_best_metric is None:
            return None
        return self._mgr.best_step()

    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def restore_latest(self, abstract_state: PyTree) -> tuple[PyTree, int] | None:
        """Restore the newest GOOD checkpoint, or None if none loads —
        the restore-on-start path (``tensorflow_mnist.py:162-167``),
        hardened into a fallback chain: each candidate step is verified
        against its integrity manifest first (size + checksum of every
        file), and a step that fails verification — or whose restore
        raises — is quarantined (renamed to ``quarantined-<step>-<k>``,
        ``ckpt_quarantined`` emitted) and the chain falls back to the next
        older step. A pod killed mid-write or a bit-flipped file can
        therefore never brick the job; it costs exactly the steps since
        the previous good save.

        ``abstract_state`` is a matching pytree (concrete arrays or
        ShapeDtypeStructs) used to restore with correct shardings.
        """
        while True:
            steps = ckpt_paths.steps_on_disk(self.directory)
            if not steps:
                return None
            step = steps[-1]
            problem = ckpt_paths.verify_manifest(self.directory, step)
            if problem is None:
                try:
                    return self._restore_step(step, abstract_state)
                except Exception as e:   # noqa: BLE001 — any torn read
                    problem = f"restore raised {type(e).__name__}: {e}"
            self._quarantine(step, problem)

    def _quarantine(self, step: int, reason: str) -> None:
        dst = ckpt_paths.quarantine_step(self.directory, step, reason)
        self.quarantined.append((step, reason))
        print(f"checkpoint step {step} quarantined -> {dst}: {reason}",
              file=sys.stderr, flush=True)
        if self.metrics is not None:
            self.metrics.emit("ckpt_quarantined", step=step, reason=reason,
                              moved_to=dst)
        # The manager caches its step list; after the rename it must
        # re-scan or later restores/saves reference a vanished dir.
        self._mgr.reload()

    def restore_best(self, abstract_state: PyTree) -> tuple[PyTree, int] | None:
        """Restore the best checkpoint by the tracked metric (best-model
        export path) — distinct from :meth:`restore_latest`, which serves
        crash-resume and may be newer than the best."""
        return self._restore_step(self.best_step(), abstract_state)

    def _restore_step(self, step: int | None,
                      abstract_state: PyTree) -> tuple[PyTree, int] | None:
        if step is None:
            return None
        # Abstract-ify BEFORE the portable transform: a concrete template
        # (the restore-on-start path passes the live state) would make
        # to_portable compute real layout reshapes whose values are
        # immediately discarded — on the interleaved pipeline that is a
        # device round-trip per block leaf for nothing.
        abstract_state = jax.tree.map(
            lambda x: x if isinstance(x, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(jax.numpy.shape(x), x.dtype,
                                      sharding=getattr(x, "sharding", None)),
            abstract_state)
        if self._to_portable is not None:
            # The on-disk layout is the portable one: build the restore
            # template in that layout, then map back to the trainer's.
            abstract_state = self._to_portable(abstract_state)
        ref = abstract_state
        state = self._mgr.restore(step, args=ocp.args.StandardRestore(ref))
        if self._from_portable is not None:
            state = self._from_portable(state)
        return state, step

    def restore_params(self, key: str = "params",
                       sharding: "jax.sharding.Sharding | None" = None
                       ) -> tuple[PyTree, int] | None:
        """Restore ONLY the *key* subtree of the newest checkpoint (inference
        path): every other leaf is an ``ocp.PLACEHOLDER``, so optimizer
        moments are never read or materialized, and the caller needs no
        knowledge of which optimizer the training run used. The tree shape
        comes from the checkpoint's own metadata — no model/optimizer
        skeleton required.

        *sharding* places the restored arrays on the CURRENT topology
        (default: replicated across this process's devices). Never restores
        with save-time shardings, so a checkpoint written on an N-chip mesh
        loads on a different machine shape (Orbax's "populate sharding from
        file" path is explicitly avoided — it references save-time devices).
        """
        step = self._mgr.latest_step()
        if step is None:
            return None
        if sharding is None:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            sharding = NamedSharding(
                Mesh(jax.devices(), ("_restore",)), PartitionSpec())
        path = os.path.join(self.directory, str(step), "default")
        ckptr = ocp.PyTreeCheckpointer()
        meta = ckptr.metadata(path).item_metadata
        tree = meta.tree if hasattr(meta, "tree") else meta

        def to_abstract(p, m):
            in_key = any(
                getattr(x, "key", getattr(x, "name", None)) == key for x in p)
            if not in_key:
                return ocp.PLACEHOLDER
            return jax.ShapeDtypeStruct(m.shape, m.dtype, sharding=sharding)

        abstract = jax.tree_util.tree_map_with_path(to_abstract, tree)
        # Explicit restore_args carry the target sharding into orbax — without
        # them PyTreeRestore falls back to the persisted sharding file, which
        # references save-time devices and fails on a different topology.
        is_leaf = lambda x: x is ocp.PLACEHOLDER or isinstance(
            x, jax.ShapeDtypeStruct)
        restore_args = jax.tree.map(
            lambda x: (ocp.ArrayRestoreArgs(sharding=sharding)
                       if isinstance(x, jax.ShapeDtypeStruct)
                       else ocp.RestoreArgs()),
            abstract, is_leaf=is_leaf)
        restored = ckptr.restore(path, args=ocp.args.PyTreeRestore(
            item=abstract, restore_args=restore_args))

        def collapse(node):
            # flax Partitioned boxes serialize as a {'value': ...} dict level;
            # strip them so callers get plain param arrays (unboxed form).
            if isinstance(node, dict):
                if set(node) == {"value"}:
                    return collapse(node["value"])
                return {k: collapse(v) for k, v in node.items()}
            return node

        # Orbax versions differ on honoring ShapeDtypeStruct.sharding in
        # PyTreeRestore; device_put enforces the documented current-topology
        # placement regardless.
        return jax.device_put(collapse(restored[key]), sharding), step

    def close(self) -> None:
        self._mgr.wait_until_finished()   # drain async saves before closing
        self._flush_manifests()
        self._mgr.close()
