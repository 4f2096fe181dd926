"""Sharded, atomic, async checkpointing with elastic restore.

Layout per step::

    <dir>/step_<k>.tmp/          # written first
        manifest.json            # tree structure, shapes, dtypes, mesh shape
        arr_<i>.npy              # one file per leaf (host-gathered)
    <dir>/step_<k>/              # atomic rename on completion

* **atomic** — a crashed writer never leaves a readable-but-corrupt step;
  restore picks the newest complete directory.
* **async** — `save(..., blocking=False)` snapshots to host memory and
  writes on a background thread; training continues.
* **elastic** — the manifest stores logical shapes only, so a checkpoint
  written on one mesh restores onto any other mesh (`restore_resharded`
  re-applies the current sharding rules) — elastic scaling across restarts.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np



def _flatten_with_paths(tree: Any) -> Tuple[List[Tuple[str, Any]], Any]:
    flat, treedef = jax.tree.flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out.append((key, leaf))
    return out, treedef


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread: Optional[threading.Thread] = None
        # serializes publish (rename) + GC: without it a blocking save can
        # overlap an in-flight async write and GC against a half-published
        # directory listing, deleting steps that should have been retained
        self._io_lock = threading.Lock()

    # ------------------------------------------------------------------
    def save(self, step: int, tree: Any, blocking: bool = True,
             extra: Optional[Dict] = None) -> None:
        # snapshot to host memory first (cheap; device → host copy)
        host_tree = jax.tree.map(lambda x: np.asarray(x), tree)
        # never overlap writes: a blocking save issued while an async write
        # is still in flight must drain it first (write order = save order,
        # so GC's newest-K decision matches the caller's step order)
        self.wait()
        if blocking:
            self._write(step, host_tree, extra)
        else:
            self._thread = threading.Thread(
                target=self._write, args=(step, host_tree, extra),
                daemon=True)
            self._thread.start()

    def wait(self) -> None:
        t = self._thread
        if t is not None:
            t.join()
            self._thread = None

    def _write(self, step: int, host_tree: Any, extra: Optional[Dict]) -> None:
        tmp = os.path.join(self.directory, f"step_{step}.tmp")
        final = os.path.join(self.directory, f"step_{step}")
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        leaves, _ = _flatten_with_paths(host_tree)
        manifest = {"step": step, "leaves": [], "extra": extra or {}}
        for i, (key, leaf) in enumerate(leaves):
            arr = np.asarray(leaf)
            np.save(os.path.join(tmp, f"arr_{i}.npy"), arr)
            manifest["leaves"].append(
                {"key": key, "file": f"arr_{i}.npy",
                 "shape": list(arr.shape), "dtype": str(arr.dtype)})
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        with self._io_lock:
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)   # atomic publish
            self._gc()

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s}"),
                          ignore_errors=True)

    # ------------------------------------------------------------------
    def all_steps(self) -> List[int]:
        out = []
        for name in os.listdir(self.directory):
            if name.startswith("step_") and not name.endswith(".tmp"):
                path = os.path.join(self.directory, name)
                if os.path.exists(os.path.join(path, "manifest.json")):
                    out.append(int(name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def load_arrays(self, step: Optional[int] = None
                    ) -> Tuple[Dict[str, np.ndarray], Dict]:
        """Template-free restore: ``(key → array, extra)`` of one step.

        The manifest records each leaf's key/shape/dtype, so a caller that
        knows its own layout (e.g. the streaming-engine recovery layer,
        which may *rescale* lanes on restore) can read a checkpoint without
        first building a shape-identical template tree.
        """
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.directory}")
        path = os.path.join(self.directory, f"step_{step}")
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        arrays = {leaf["key"]: np.load(os.path.join(path, leaf["file"]))
                  for leaf in manifest["leaves"]}
        return arrays, manifest["extra"]

    def restore(self, template: Any, step: Optional[int] = None
                ) -> Tuple[Any, Dict]:
        """Restore into the structure of `template` (shapes must match)."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.directory}")
        path = os.path.join(self.directory, f"step_{step}")
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        arrays = {}
        for leaf in manifest["leaves"]:
            arrays[leaf["key"]] = np.load(os.path.join(path, leaf["file"]))
        leaves, treedef = _flatten_with_paths(template)
        restored = []
        for key, tmpl in leaves:
            if key not in arrays:
                raise KeyError(f"checkpoint missing leaf {key}")
            arr = arrays[key]
            want = tuple(np.shape(tmpl))
            if tuple(arr.shape) != want:
                raise ValueError(f"shape mismatch for {key}: "
                                 f"{arr.shape} vs {want}")
            restored.append(arr.astype(np.asarray(tmpl).dtype
                                       if hasattr(tmpl, "dtype") else arr.dtype))
        tree = jax.tree.unflatten(treedef, restored)
        return tree, manifest["extra"]


def restore_resharded(manager: CheckpointManager, template: Any,
                      shardings: Any, step: Optional[int] = None
                      ) -> Tuple[Any, Dict]:
    """Restore a checkpoint and place it under new shardings (elastic
    restart onto a different mesh: the checkpoint stores logical arrays,
    `jax.device_put` re-shards them under the new topology)."""
    tree, extra = manager.restore(template, step)
    placed = jax.tree.map(
        lambda arr, s: jax.device_put(arr, s), tree, shardings)
    return placed, extra
