"""Checkpoint / restore of pytrees — the writer under simcheck.py (DESIGN.md §7.5).

Design goals for long runs:
  * **Atomic**: write to a tmp dir, fsync, rename — a preempted write never
    corrupts the latest checkpoint.
  * **Async**: ``save_async`` snapshots device arrays to host (cheap) and
    writes on a background thread — the simulation continues immediately.
  * **Elastic**: restore() only needs the *tree*; arrays are ``device_put``
    with whatever sharding the *current* mesh prescribes, so a run checkpointed
    on four devices restarts on fewer (or one CPU) unchanged.
  * **Self-describing**: a manifest (step, tree structure, shapes/dtypes)
    travels with the data; restore validates structural compatibility.

Format: one .npz of flattened leaves + a JSON manifest. (numpy-only: no
external checkpoint dependency is available in this environment.)
"""

from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Dict, Optional

import jax
import numpy as np

_MANIFEST = "manifest.json"
_DATA = "arrays.npz"


def _flatten_with_paths(tree: Any):
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    flat = {}
    for path, leaf in leaves_with_paths:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        flat[key] = leaf
    return flat


def save(ckpt_dir: str, step: int, tree: Any,
         extras: Optional[Dict] = None) -> str:
    """Synchronous atomic save. Returns the checkpoint path.

    ``extras``: optional JSON-serializable dict stored in the manifest —
    side-band metadata the arrays alone cannot carry (the simulation
    checkpoints record rung/degradation knobs here; core/simcheck.py).
    """
    import ml_dtypes  # ships with jax

    flat = _flatten_with_paths(tree)
    host = {k: np.asarray(v) for k, v in flat.items()}
    # npz cannot store ml_dtypes (bf16): persist as uint16 views, record the
    # true dtype in the manifest
    stored = {}
    for k, v in host.items():
        if v.dtype == ml_dtypes.bfloat16:
            stored[k] = v.view(np.uint16)
        else:
            stored[k] = v
    path = os.path.join(ckpt_dir, f"step_{step:09d}")
    tmp = path + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    np.savez(os.path.join(tmp, _DATA), **stored)
    manifest = {"step": step,
                "leaves": {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                           for k, v in host.items()}}
    if extras is not None:
        manifest["extras"] = extras
    with open(os.path.join(tmp, _MANIFEST), "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    if os.path.exists(path):
        shutil.rmtree(path)
    os.rename(tmp, path)
    _update_latest(ckpt_dir, step)
    return path


def _update_latest(ckpt_dir: str, step: int) -> None:
    tmp = os.path.join(ckpt_dir, "LATEST.tmp")
    with open(tmp, "w") as f:
        f.write(str(step))
        f.flush()
        os.fsync(f.fileno())
    os.rename(tmp, os.path.join(ckpt_dir, "LATEST"))


class AsyncCheckpointer:
    """Snapshot-to-host then write on a daemon thread (off the critical path)."""

    def __init__(self, ckpt_dir: str, keep: int = 3):
        self.ckpt_dir = ckpt_dir
        self.keep = keep
        self._thread: Optional[threading.Thread] = None

    def save_async(self, step: int, tree: Any,
                   extras: Optional[Dict] = None) -> None:
        self.wait()
        host = jax.tree.map(np.asarray, tree)   # device→host snapshot (blocking
        # only for the copy, not the write)

        def _write():
            save(self.ckpt_dir, step, host, extras=extras)
            self._gc()

        self._thread = threading.Thread(target=_write, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self) -> None:
        steps = list_steps(self.ckpt_dir)
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.ckpt_dir, f"step_{s:09d}"),
                          ignore_errors=True)
        # stale .tmp dirs are crash debris from an interrupted save — never a
        # live write, since saves on one checkpointer are serialized by wait()
        for name in os.listdir(self.ckpt_dir):
            if name.startswith("step_") and name.endswith(".tmp"):
                shutil.rmtree(os.path.join(self.ckpt_dir, name),
                              ignore_errors=True)


def list_steps(ckpt_dir: str):
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for name in os.listdir(ckpt_dir):
        if name.startswith("step_") and not name.endswith(".tmp"):
            out.append(int(name[len("step_"):]))
    return sorted(out)


def latest_step(ckpt_dir: str) -> Optional[int]:
    # Any non-.tmp step dir is complete (atomic rename), and a crash between
    # the rename and the LATEST update leaves LATEST pointing one save back —
    # so the directory listing, not LATEST, is authoritative (LATEST stays
    # on disk as a human-readable hint only).
    steps = list_steps(ckpt_dir)
    return steps[-1] if steps else None


def load_manifest(ckpt_dir: str, step: int) -> Dict:
    """The manifest of one checkpoint (step, leaves, optional extras)."""
    path = os.path.join(ckpt_dir, f"step_{step:09d}", _MANIFEST)
    with open(path) as f:
        return json.load(f)


def restore(ckpt_dir: str, step: int, like: Any,
            shardings: Any = None) -> Any:
    """Restore into the structure of ``like``; reshard onto the current mesh.

    ``shardings``: optional pytree (same structure) of NamedSharding — elastic
    restarts pass the *new* mesh's shardings here.
    """
    import ml_dtypes

    path = os.path.join(ckpt_dir, f"step_{step:09d}")
    with open(os.path.join(path, _MANIFEST)) as f:
        manifest = json.load(f)
    raw = np.load(os.path.join(path, _DATA))
    data = {}
    for k in raw.files:
        arr = raw[k]
        if manifest["leaves"][k]["dtype"] == "bfloat16":
            arr = arr.view(ml_dtypes.bfloat16)
        data[k] = arr
    flat_like = _flatten_with_paths(like)
    missing = set(flat_like) - set(manifest["leaves"])
    extra = set(manifest["leaves"]) - set(flat_like)
    if missing or extra:
        raise ValueError(f"checkpoint structure mismatch: missing={missing} "
                         f"extra={extra}")
    flat_sh = _flatten_with_paths(shardings) if shardings is not None else {}

    out_flat = {}
    for key, leaf in flat_like.items():
        arr = data[key]
        want = tuple(np.asarray(leaf).shape) if not hasattr(leaf, "shape") \
            else tuple(leaf.shape)
        if tuple(arr.shape) != want:
            raise ValueError(f"{key}: shape {arr.shape} != expected {want}")
        if key in flat_sh:
            out_flat[key] = jax.device_put(arr, flat_sh[key])
        else:
            out_flat[key] = jax.numpy.asarray(arr)
    # rebuild tree in like's structure
    leaves_with_paths, treedef = jax.tree_util.tree_flatten_with_path(like)
    ordered = []
    for pth, _ in leaves_with_paths:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in pth)
        ordered.append(out_flat[key])
    return jax.tree_util.tree_unflatten(treedef, ordered)
