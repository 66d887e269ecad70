"""Atomic, sharding-aware, elastic checkpointing.

Design (scaled mentally to 1000+ nodes, implemented for this container):

* Arrays are stored at their *logical* (global) shapes, one ``.npy`` per
  pytree leaf plus a msgpack-free JSON manifest. On a multi-host cluster
  each host writes only the shards it owns into a per-leaf directory and
  host 0 writes the manifest; here (single process) fully-addressable
  arrays are written directly. Restore re-shards to *any* mesh — the
  elastic-rescale path: load global array, device_put with the new
  sharding.
* Atomicity: write to ``step_N.tmp/``, fsync, rename to ``step_N/``. A
  crash mid-write never corrupts the latest complete checkpoint.
* Retention: keep the newest ``keep`` checkpoints (the scheduler may
  restart the job against any of them).
* ``latest_step`` scans for complete checkpoints only.
"""
from __future__ import annotations

import json
import os
import re
import shutil
from typing import Optional

import numpy as np
import jax

__all__ = ["save_checkpoint", "restore_checkpoint", "read_manifest",
           "latest_step", "available_steps"]

_STEP_RE = re.compile(r"^step_(\d+)$")


def _flatten(tree):
    leaves, treedef = jax.tree.flatten_with_path(tree)
    return [(jax.tree_util.keystr(p), x) for p, x in leaves], treedef


def save_checkpoint(directory: str, step: int, tree, keep: int = 3) -> str:
    """Write pytree ``tree`` at ``directory/step_<step>``. Returns path."""
    os.makedirs(directory, exist_ok=True)
    final = os.path.join(directory, f"step_{step}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    named, _ = _flatten(tree)
    manifest = {"step": step, "leaves": []}
    for i, (name, x) in enumerate(named):
        arr = np.asarray(jax.device_get(x))
        shape = arr.shape  # before ascontiguousarray (it promotes 0-d to 1-d)
        arr = np.ascontiguousarray(arr)
        fn = f"leaf_{i}.npy"
        # store the raw byte view: ml_dtypes (bfloat16) do not roundtrip
        # through npy dtype descriptors on plain numpy loads
        np.save(os.path.join(tmp, fn), arr.reshape(-1).view(np.uint8))
        manifest["leaves"].append({"name": name, "file": fn,
                                   "shape": list(shape),
                                   "dtype": str(arr.dtype)})
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    _retain(directory, keep)
    return final


def _retain(directory: str, keep: int):
    steps = sorted(available_steps(directory))
    for s in steps[:-keep]:
        shutil.rmtree(os.path.join(directory, f"step_{s}"), ignore_errors=True)


def available_steps(directory: str):
    """Complete checkpoints only (manifest present)."""
    if not os.path.isdir(directory):
        return []
    out = []
    for d in os.listdir(directory):
        m = _STEP_RE.match(d)
        if m and os.path.exists(os.path.join(directory, d, "manifest.json")):
            out.append(int(m.group(1)))
    return sorted(out)


def latest_step(directory: str) -> Optional[int]:
    steps = available_steps(directory)
    return steps[-1] if steps else None


def read_manifest(directory: str, step: int) -> dict:
    """The step's manifest (leaf names/shapes/dtypes). Lets a caller
    reconstruct the ``like`` pytree for ``restore_checkpoint`` without
    knowing the saved structure a priori — the self-describing-restore
    path (``repro.index.snapshot`` rebuilds whole indexes from it)."""
    path = os.path.join(directory, f"step_{step}", "manifest.json")
    with open(path) as f:
        return json.load(f)


def restore_checkpoint(directory: str, step: int, like, shardings=None):
    """Restore into the structure of ``like`` (pytree of arrays or
    ShapeDtypeStructs). ``shardings``: optional matching pytree of
    NamedShardings — the elastic-rescale path (any mesh works).
    """
    path = os.path.join(directory, f"step_{step}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    named_like, treedef = _flatten(like)
    by_name = {e["name"]: e for e in manifest["leaves"]}
    shard_leaves = (jax.tree.leaves(shardings) if shardings is not None
                    else [None] * len(named_like))
    if shardings is not None:
        assert len(shard_leaves) == len(named_like)
    out = []
    for (name, proto), shd in zip(named_like, shard_leaves):
        entry = by_name.get(name)
        if entry is None:
            raise KeyError(f"checkpoint at {path} missing leaf {name}")
        raw = np.load(os.path.join(path, entry["file"]))
        stored_dtype = np.dtype(jax.numpy.dtype(entry["dtype"]))
        stored_shape = tuple(entry["shape"])
        arr = raw.reshape(-1).view(stored_dtype).reshape(stored_shape)
        want_shape = tuple(proto.shape)
        if tuple(arr.shape) != want_shape:
            raise ValueError(f"{name}: checkpoint shape {arr.shape} != {want_shape}")
        arr = arr.astype(proto.dtype)
        out.append(jax.device_put(arr, shd) if shd is not None
                   else jax.numpy.asarray(arr))
    return jax.tree.unflatten(treedef, out)
