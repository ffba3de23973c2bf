"""Sharded, manifest-committed checkpointing with elastic restore.

Layout (one directory per step):

  <root>/step_000042.tmp/      # written first
    leaf_00000.npy ...         # one file per pytree leaf
    manifest.json              # treedef, shapes, dtypes, step, written last
  <root>/step_000042/          # atomic rename after manifest fsync

Crash safety: a checkpoint exists iff the final rename happened; partial
writes are invisible (".tmp" dirs are garbage-collected on open).  On a
real multi-host deployment each host writes only the shards it owns
(``process_index`` prefix); this container is single-process, so files
hold full arrays but restore still goes through ``jax.device_put`` with
target shardings — restoring onto a *different* mesh (elastic re-shard)
is exercised in tests.

Async: ``save(..., blocking=False)`` snapshots to host RAM immediately
(donation-safe) and writes on a background thread; ``wait()`` joins.
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import threading
from typing import Any, Optional

import jax
import numpy as np


def _flatten_with_names(tree):
    leaves, treedef = jax.tree.flatten(tree)
    paths = [jax.tree_util.keystr(p)
             for p, _ in jax.tree.leaves_with_path(tree)]
    return leaves, paths, treedef


def save(path: os.PathLike, tree: Any, step: int,
         extra: Optional[dict] = None) -> pathlib.Path:
    """Blocking sharded save with atomic commit."""
    root = pathlib.Path(path)
    root.mkdir(parents=True, exist_ok=True)
    final = root / f"step_{step:08d}"
    tmp = root / f"step_{step:08d}.tmp"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)
    leaves, names, _ = _flatten_with_names(tree)
    manifest = {"step": step, "leaves": [], "extra": extra or {}}
    for i, (leaf, name) in enumerate(zip(leaves, names)):
        arr = np.asarray(leaf)
        logical_dtype = str(arr.dtype)
        if arr.dtype.kind == "V" or logical_dtype == "bfloat16":
            # ml_dtypes (bf16/fp8) are not npy-serializable: store the
            # raw bits and record the logical dtype in the manifest.
            arr = arr.view(np.uint16 if logical_dtype == "bfloat16"
                           else np.uint8)
        fn = f"leaf_{i:05d}.npy"
        np.save(tmp / fn, arr)
        manifest["leaves"].append(
            {"name": name, "file": fn, "shape": list(arr.shape),
             "dtype": logical_dtype})
    mpath = tmp / "manifest.json"
    with open(mpath, "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    if final.exists():
        shutil.rmtree(final)
    tmp.rename(final)
    return final


def latest_step(path: os.PathLike) -> Optional[int]:
    root = pathlib.Path(path)
    if not root.exists():
        return None
    # GC partial writes
    for tmp in root.glob("step_*.tmp"):
        shutil.rmtree(tmp, ignore_errors=True)
    steps = sorted(int(p.name.split("_")[1])
                   for p in root.glob("step_*") if p.is_dir()
                   and (p / "manifest.json").exists())
    return steps[-1] if steps else None


def restore(path: os.PathLike, like: Any, step: Optional[int] = None,
            shardings: Any = None) -> tuple:
    """Restore into the structure of ``like``.  ``shardings`` (optional
    pytree matching ``like``) re-shards onto the *current* mesh — the
    elastic-restart path (the saved mesh may have had a different size).
    Returns (tree, step, extra)."""
    root = pathlib.Path(path)
    if step is None:
        step = latest_step(root)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {root}")
    d = root / f"step_{step:08d}"
    manifest = json.loads((d / "manifest.json").read_text())
    leaves, names, treedef = _flatten_with_names(like)
    assert len(leaves) == len(manifest["leaves"]), \
        f"checkpoint has {len(manifest['leaves'])} leaves, " \
        f"expected {len(leaves)}"
    sh_leaves = (treedef.flatten_up_to(shardings)
                 if shardings is not None else [None] * len(leaves))
    import ml_dtypes

    out = []
    for rec, leaf, sh in zip(manifest["leaves"], leaves, sh_leaves):
        arr = np.load(d / rec["file"])
        if rec["dtype"] == "bfloat16":
            arr = arr.view(ml_dtypes.bfloat16)
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(
                f"{rec['name']}: shape {arr.shape} != {leaf.shape}")
        if sh is not None:
            out.append(jax.device_put(arr.astype(leaf.dtype), sh))
        else:
            out.append(jax.numpy.asarray(arr, dtype=leaf.dtype))
    return treedef.unflatten(out), manifest["step"], manifest["extra"]


class CheckpointManager:
    """Async writer + retention policy."""

    def __init__(self, path: os.PathLike, keep: int = 3):
        self.path = pathlib.Path(path)
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        self.saved_steps: list = []

    def save(self, tree: Any, step: int, extra: Optional[dict] = None,
             blocking: bool = True) -> None:
        # snapshot to host before the training step can donate buffers
        host_tree = jax.tree.map(lambda x: np.asarray(x), tree)

        def work():
            save(self.path, host_tree, step, extra)
            self.saved_steps.append(step)
            self._retain()

        self.wait()
        if blocking:
            work()
        else:
            self._thread = threading.Thread(target=work, daemon=True)
            self._thread.start()

    def _retain(self) -> None:
        steps = sorted(int(p.name.split("_")[1])
                       for p in self.path.glob("step_*") if p.is_dir())
        for s in steps[: -self.keep]:
            shutil.rmtree(self.path / f"step_{s:08d}",
                          ignore_errors=True)

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def restore_latest(self, like: Any, shardings: Any = None):
        self.wait()
        return restore(self.path, like, shardings=shardings)
