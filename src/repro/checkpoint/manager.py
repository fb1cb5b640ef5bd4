"""Fault-tolerant checkpointing with elastic re-shard on restore.

Design (no tensorstore in this container, so .npz shards + a JSON manifest):

* **Atomicity**: a checkpoint directory is written under ``step_<n>.tmp`` and
  os.rename'd into place only after every shard and the manifest have been
  fsync'd — a job killed mid-write can never leave a "latest" that is
  half-written, so restart always finds a valid step.
* **Elasticity**: ``restore(..., mesh=new_mesh, specs=...)`` re-shards on
  load via jax.device_put against the *new* mesh — the saved artifact is
  mesh-agnostic (full arrays per leaf), so a job can come back on a different
  device count (scale up/down after node failures).
* **Retention**: keep_last prunes old steps; a corrupt/partial dir (no
  manifest) is ignored by ``latest_step`` and garbage-collected.
* **Async**: ``save(..., blocking=False)`` runs serialization in a worker
  thread so the train loop's critical path only pays for the host transfer.

On a real multi-host fleet each host writes only its addressable shards and
the manifest records the global shape/sharding — the single-process container
degenerates to full arrays, same format.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Optional

import jax
import numpy as np

from ..obs import get_journal

__all__ = ["CheckpointManager", "save_tree", "restore_tree"]

_MANIFEST = "manifest.json"


def _to_npz_safe(arr: np.ndarray) -> np.ndarray:
    """npz cannot store ml_dtypes (bf16, fp8); persist those as flat bytes
    (shape+dtype live in the manifest)."""
    if arr.dtype.kind in "biufc":   # standard numeric dtypes round-trip
        return arr
    return np.ascontiguousarray(arr).reshape(-1).view(np.uint8)


def _from_npz_safe(arr: np.ndarray, dtype_name: str, shape) -> np.ndarray:
    if arr.dtype.name == dtype_name:      # stored natively
        return arr
    import ml_dtypes  # jax dependency, always present
    dt = np.dtype(getattr(ml_dtypes, dtype_name))
    return arr.view(dt).reshape(shape)


def _flatten_with_names(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
             for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return names, leaves, treedef


def save_tree(path: str, tree: Any, step: int) -> None:
    """Atomic write of a pytree snapshot into ``path`` (a step directory).

    The tmp dir is writer-unique (pid-suffixed) so two fenced writers — a
    lease victim and the worker that stole its shard — never collide on the
    staging dir; shard results are deterministic, so whichever rename lands
    last publishes the same bits."""
    tmp = f"{path}.tmp-{os.getpid()}"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    names, leaves, _ = _flatten_with_names(tree)
    host = [np.asarray(jax.device_get(l)) for l in leaves]
    arrs = {f"leaf_{i}": _to_npz_safe(h) for i, h in enumerate(host)}
    np.savez(os.path.join(tmp, "shards.npz"), **arrs)
    manifest = {
        "step": step,
        "names": names,
        "dtypes": [h.dtype.name for h in host],
        "shapes": [list(np.shape(l)) for l in leaves],
        "format": 1,
    }
    mpath = os.path.join(tmp, _MANIFEST)
    with open(mpath, "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    try:
        if os.path.exists(path):
            shutil.rmtree(path)
        os.rename(tmp, path)    # atomic publish
    except OSError:
        # a concurrent fenced writer won the rename; its snapshot is
        # byte-equivalent (deterministic recompute), so losing the race IS
        # a successful publish — drop our staging dir and move on
        if os.path.exists(os.path.join(path, _MANIFEST)):
            shutil.rmtree(tmp, ignore_errors=True)
        else:
            raise


def restore_tree(path: str, like: Any, *, mesh=None, specs=None,
                 host: bool = False) -> Any:
    """Load a snapshot; optionally re-shard onto ``mesh`` with ``specs``.

    ``like`` provides the pytree structure (its leaf values are ignored).
    ``host=True`` keeps every leaf a NumPy array and never touches a JAX
    backend — for supervisors that must leave the chip to their workers.
    """
    with open(os.path.join(path, _MANIFEST)) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(path, "shards.npz"))
    names, _, treedef = _flatten_with_names(like)
    if names != manifest["names"]:
        raise ValueError(
            "checkpoint tree mismatch:\n saved=%s\n want=%s"
            % (manifest["names"][:5], names[:5]))
    leaves = [_from_npz_safe(data[f"leaf_{i}"], manifest["dtypes"][i],
                             manifest["shapes"][i])
              for i in range(len(names))]
    if host:
        return jax.tree_util.tree_unflatten(treedef, leaves)
    if mesh is not None and specs is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        spec_flat = jax.tree_util.tree_flatten(
            specs, is_leaf=lambda x: x is None or isinstance(x, PartitionSpec))[0]
        if len(spec_flat) != len(leaves):
            raise ValueError("spec tree does not match checkpoint tree")
        leaves = [
            jax.device_put(leaf, NamedSharding(mesh, sp)) if sp is not None
            else jax.device_put(leaf)
            for leaf, sp in zip(leaves, spec_flat)]
    else:
        leaves = [_put_preserving_dtype(l) for l in leaves]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _put_preserving_dtype(leaf: np.ndarray):
    """device_put unless it would silently change the saved dtype.

    With jax x64 disabled, device_put downcasts float64/int64 leaves to
    32-bit — which corrupted e.g. restored CommLedger counters above 2^24.
    Such leaves stay as host numpy arrays at their manifest dtype; callers
    that need them on device opted into 32-bit anyway."""
    out = jax.device_put(leaf)
    return leaf if out.dtype != leaf.dtype else out


class CheckpointManager:
    """Directory layout: <root>/step_<n>/{shards.npz, manifest.json}.

    ``on_save`` (optional) is invoked synchronously with the step number at
    the top of every ``save`` — the chunk-boundary hook the fleet uses for
    heartbeat touches, lease renewals, and chaos injection, with no
    branches in the runtime's chunk driver.

    ``pin(step)`` / ``unpin(step)`` exempt a step from ``keep_last``
    retention: a pinned step is never garbage-collected, however many newer
    steps churn past it. Pins are durable marker files (``pin_<n>``) in the
    root — a restarted process (or a different one sharing the directory)
    sees them — which is what lets the serving layer keep its "last good
    served subspace" alive while per-tick service snapshots cycle."""

    def __init__(self, root: str, keep_last: int = 3, on_save=None):
        self.root = root
        self.keep_last = keep_last
        self.on_save = on_save
        os.makedirs(root, exist_ok=True)
        self._worker: Optional[threading.Thread] = None

    def _step_dir(self, step: int) -> str:
        return os.path.join(self.root, f"step_{step:08d}")

    def _pin_path(self, step: int) -> str:
        return os.path.join(self.root, f"pin_{step:08d}")

    # -- retention pins -----------------------------------------------------
    def pin(self, step: int) -> None:
        """Exempt ``step`` from GC until ``unpin`` (durable across restarts)."""
        with open(self._pin_path(step), "w") as f:
            f.write(str(step))
            f.flush()
            os.fsync(f.fileno())
        get_journal().event("ckpt_pin", "checkpoint", step=step)

    def unpin(self, step: int) -> None:
        try:
            os.remove(self._pin_path(step))
        except FileNotFoundError:
            return
        get_journal().event("ckpt_unpin", "checkpoint", step=step)

    def pinned_steps(self):
        out = []
        for name in os.listdir(self.root):
            if name.startswith("pin_"):
                try:
                    out.append(int(name.split("_")[1]))
                except ValueError:
                    continue
        return sorted(out)

    def all_steps(self):
        out = []
        for name in os.listdir(self.root):
            full = os.path.join(self.root, name)
            # ".tmp" anywhere excludes both legacy "step_N.tmp" staging
            # dirs and the writer-unique "step_N.tmp-<pid>" form; a torn
            # dir (no manifest — e.g. chaos deleted it mid-step) is
            # skipped the same way so latest_step never lands on it
            if name.startswith("step_") and ".tmp" not in name \
                    and os.path.exists(os.path.join(full, _MANIFEST)):
                out.append(int(name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def save(self, step: int, tree: Any, blocking: bool = True) -> None:
        # The span OPENS before on_save fires: a chaos kill injected at the
        # boundary hook leaves an orphaned span_start in the journal, which
        # is exactly how forensics names the phase the worker died in. The
        # span covers the caller-visible critical path — for async saves
        # that is the host transfer + thread handoff, not the write itself
        # (the worker thread journals ckpt_write when it lands).
        sp = get_journal().begin("ckpt_save", "checkpoint", step=step,
                                 blocking=blocking)
        self.wait()  # never two writers
        if self.on_save is not None:
            self.on_save(step)
        if blocking:
            self._save(step, tree)
        else:
            host_tree = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)
            self._worker = threading.Thread(
                target=self._save, args=(step, host_tree, True), daemon=True)
            self._worker.start()
        sp.end()

    def _save(self, step: int, tree: Any, async_write: bool = False) -> None:
        save_tree(self._step_dir(step), tree, step)
        if async_write:
            # only the async path marks write completion separately — it
            # lands after the caller's ckpt_save span closed; a blocking
            # save's span end IS the completion record
            get_journal().event("ckpt_write", "checkpoint", step=step)
        self._gc()

    def wait(self) -> None:
        if self._worker is not None:
            self._worker.join()
            self._worker = None

    def restore(self, like: Any, step: Optional[int] = None, *,
                mesh=None, specs=None):
        self.wait()
        step = self.latest_step() if step is None else step
        if step is None:
            return None, None
        with get_journal().span("ckpt_restore", "checkpoint", step=step):
            tree = restore_tree(self._step_dir(step), like, mesh=mesh,
                                specs=specs)
        return tree, step

    def _gc(self) -> None:
        # remove stale tmp dirs (crashed writers, any ".tmp"/".tmp-<pid>"
        # suffix) and old steps
        for name in os.listdir(self.root):
            if ".tmp" in name:
                shutil.rmtree(os.path.join(self.root, name), ignore_errors=True)
        steps = self.all_steps()
        pinned = set(self.pinned_steps())
        removed = []
        for s in steps[:-self.keep_last] if self.keep_last else []:
            if s in pinned:
                continue   # pinned steps survive keep_last churn
            shutil.rmtree(self._step_dir(s), ignore_errors=True)
            removed.append(s)
        if removed:
            get_journal().event("ckpt_gc", "checkpoint", removed=removed,
                                pinned=sorted(pinned))
