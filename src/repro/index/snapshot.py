"""Index durability: snapshot/restore through ``repro.checkpoint``.

A snapshot is one atomic checkpoint step holding a flat pytree of the
store's device/host state — per-segment packed words, validity bitmasks,
external ids, band hashes — plus a JSON metadata leaf (geometry,
``next_id``, band spec) encoded as a uint8 array so it rides the same
atomic write path as the tensors. Restore is self-describing: the
checkpoint manifest (``checkpoint.read_manifest``) supplies every leaf's
shape/dtype, so ``restore_index`` rebuilds the ``like`` pytree, the
segments, and the id→row map without any sidecar file, and the restored
store serves bit-identical results (including tie order and tombstones).

The tail is snapshotted at full buffer size with its ``length`` in the
metadata, so a restored index resumes ingestion exactly where it stopped;
``next_id`` round-trips so ids are never reused after restart.
Placement is not part of a snapshot: ``restore_index(..., devices=...)``
places the restored segments by the store's rule.
"""
from __future__ import annotations

import json
import re

import numpy as np
import jax
import jax.numpy as jnp

from repro.ann.bands import BandSpec
from repro.checkpoint import (latest_step, read_manifest,
                              restore_checkpoint, save_checkpoint)
from repro.index.segment_log import Segment, SegmentLogStore

__all__ = ["save_index", "restore_index"]

_NAME_RE = re.compile(r"\['([^']+)'\]$")


def save_index(store: SegmentLogStore, directory: str, step: int,
               keep: int = 3) -> str:
    """Write the store as checkpoint ``directory/step_<step>``."""
    segs = store.segments()
    # version 2: packed codes produced by the canonical r_unit-keyed R
    # generation with the tagged offset key (repro.encode / core.sketch).
    # Version-1 snapshots hold codes from the old block_d-keyed schedule:
    # a new sketcher would disagree with them silently, so restore
    # rejects them loudly instead.
    meta = {
        "version": 2, "k": store.k, "bits": store.bits,
        "tail_rows": store.tail_rows, "tail_len": store.tail.length,
        "next_id": store.next_id, "n_segments": len(segs),
        "impl": store.impl,
        "band": ([store.band_spec.n_tables, store.band_spec.band_width]
                 if store.band_spec else None),
    }
    tree = {"meta": np.frombuffer(json.dumps(meta).encode(), np.uint8)}
    for i, seg in enumerate(segs):
        tree[f"seg{i}_words"] = seg.words
        tree[f"seg{i}_valid"] = seg.valid
        tree[f"seg{i}_ids"] = seg.ids
        if seg.hashes is not None:
            tree[f"seg{i}_hashes"] = seg.hashes
    return save_checkpoint(directory, step, tree, keep=keep)


def _like_from_manifest(manifest: dict) -> dict:
    like = {}
    for leaf in manifest["leaves"]:
        m = _NAME_RE.match(leaf["name"])
        if m is None:
            raise ValueError(f"unexpected leaf name {leaf['name']!r}")
        like[m.group(1)] = jax.ShapeDtypeStruct(
            tuple(leaf["shape"]), jnp.dtype(leaf["dtype"]))
    return like


def restore_index(directory: str, step: int = None,
                  devices=None) -> SegmentLogStore:
    """Rebuild a ``SegmentLogStore`` from a snapshot (latest step when
    ``step`` is None), placed over ``devices`` when given. Self-describing:
    structure comes from the checkpoint manifest, geometry/id state from
    the metadata leaf."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no complete snapshot in {directory}")
    tree = restore_checkpoint(directory, step,
                              _like_from_manifest(read_manifest(directory,
                                                                step)))
    meta = json.loads(bytes(np.asarray(tree["meta"])).decode())
    if meta.get("version") != 2:
        raise ValueError(
            f"unsupported snapshot version {meta.get('version')} (v1 codes "
            f"predate the canonical r_unit key schedule and would silently "
            f"disagree with a current sketcher; re-ingest the corpus)")
    band = (BandSpec(n_tables=meta["band"][0], band_width=meta["band"][1])
            if meta["band"] else None)
    store = SegmentLogStore(meta["k"], meta["bits"], band_spec=band,
                            tail_rows=meta["tail_rows"], impl=meta["impl"],
                            devices=devices)
    n_segs = meta["n_segments"]
    for i in range(n_segs):
        is_tail = i == n_segs - 1
        words = jnp.asarray(tree[f"seg{i}_words"], jnp.uint32)
        seg = Segment(
            words=words,
            hashes=(jnp.asarray(tree[f"seg{i}_hashes"], jnp.uint32)
                    if f"seg{i}_hashes" in tree else None),
            ids=np.asarray(tree[f"seg{i}_ids"], np.int64).copy(),
            valid=np.asarray(tree[f"seg{i}_valid"], np.uint32).copy(),
            live=0,
            length=meta["tail_len"] if is_tail else words.shape[0])
        rows = seg.live_rows()
        seg.live = int(rows.size)
        store._by_id.update((int(seg.ids[row]), (seg, int(row)))
                            for row in rows)
        if is_tail:
            store.tail = seg
        else:
            store.sealed.append(seg)
    store._place()
    store.next_id = meta["next_id"]
    store.generation += 1
    return store
