"""Size-tiered compaction for the segment log.

Steady-state churn leaves the log as many tail-sized sealed segments with
a growing tombstone fraction: each query pays one kernel launch per
segment, and dead rows still burn XOR/popcount bandwidth. Compaction
rewrites *adjacent runs* of sealed segments into one dense segment —
adjacency preserves the log's iteration order, which is the search
tie-break order, so compaction is invisible to results (the bit-exactness
contract ``tests/test_index.py`` enforces).

Policy (size-tiered, greedy over the log):

* accumulate adjacent sealed segments while the merged output stays under
  ``target_rows`` live rows;
* rewrite a run when it has more than one segment (merge small segments)
  or when its single segment carries more than ``max_dead_fraction``
  tombstones (reclaim space);
* the mutable tail is never touched.

The rewrite gathers live rows on device (O(run) copy — the cost is
proportional to what is rewritten, never the whole corpus) and emits a
fully-live segment, so compaction both caps segment count and drops
tombstoned rows. On a placed store (``SegmentLogStore(devices=...)``) the
run's rows are joined on ``devices[0]``, and every segment whose log
position changed then moves to the chip its new position names.
``compact`` mutates the store in place and returns a stats dict.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax.numpy as jnp

from repro.index.segment_log import Segment, SegmentLogStore, \
    _np_pack_bitmask
from repro.obs import span

__all__ = ["CompactionPolicy", "plan_compaction", "compact"]


@dataclass(frozen=True)
class CompactionPolicy:
    target_rows: int = 4096        # max live rows in a merged segment
    max_dead_fraction: float = 0.25  # lone segment rewritten above this


def _wants_rewrite(run: list[Segment], policy: CompactionPolicy) -> bool:
    if len(run) > 1:
        return True
    seg = run[0]
    dead = seg.length - seg.live
    return seg.length > 0 and dead / seg.length > policy.max_dead_fraction


def plan_compaction(store: SegmentLogStore,
                    policy: CompactionPolicy = CompactionPolicy()):
    """Greedy adjacent runs of sealed-segment indices worth rewriting."""
    runs, cur, cur_live = [], [], 0
    for i, seg in enumerate(store.sealed):
        if cur and cur_live + seg.live > policy.target_rows:
            if _wants_rewrite([store.sealed[j] for j in cur], policy):
                runs.append(cur)
            cur, cur_live = [], 0
        cur.append(i)
        cur_live += seg.live
    if cur and _wants_rewrite([store.sealed[j] for j in cur], policy):
        runs.append(cur)
    return runs


def _rewrite_run(store: SegmentLogStore, run: list[Segment]) -> Segment:
    """Gather the run's live rows into one dense, fully-live segment."""
    rows_per = [seg.live_rows() for seg in run]
    n_new = int(sum(r.size for r in rows_per))
    words = jnp.concatenate(store._gather(
        [jnp.take(seg.words, jnp.asarray(rows), axis=0)
         for seg, rows in zip(run, rows_per) if rows.size])) \
        if n_new else jnp.zeros((0, store.n_words), jnp.uint32)
    hashes = None
    if store.band_spec is not None:
        hashes = jnp.concatenate(store._gather(
            [jnp.take(seg.hashes, jnp.asarray(rows), axis=0)
             for seg, rows in zip(run, rows_per) if rows.size])) \
            if n_new else jnp.zeros((0, store.band_spec.n_tables),
                                    jnp.uint32)
    ids = (np.concatenate([seg.ids[rows]
                           for seg, rows in zip(run, rows_per)])
           if n_new else np.zeros(0, np.int64))
    valid = _np_pack_bitmask(np.ones(n_new, bool)) if n_new \
        else np.zeros(0, np.uint32)
    return Segment(words=words, hashes=hashes, ids=ids, valid=valid,
                   live=n_new, length=n_new)


def compact(store: SegmentLogStore,
            policy: CompactionPolicy = CompactionPolicy()) -> dict:
    """Rewrite planned runs in place. Iteration order of live rows — and
    therefore every search result — is unchanged. Reports through the
    store's ``repro.obs`` registry (``index.compactions`` /
    ``index.compact_rows_dropped`` / ``index.compact_bytes_copied``) and
    opens an ``index.compact`` span when tracing."""
    with span("index.compact") as sp:
        runs = plan_compaction(store, policy)
        before = len(store.sealed)
        dropped = 0
        copied_bytes = 0
        run_at = {run[0]: run for run in runs}
        in_run = {i for run in runs for i in run}
        new_sealed: list[Segment] = []
        for i, seg in enumerate(store.sealed):
            if i not in in_run:
                new_sealed.append(seg)
                continue
            if i not in run_at:
                continue            # consumed by the run starting earlier
            run = [store.sealed[j] for j in run_at[i]]
            merged = _rewrite_run(store, run)
            sp.sync(merged.words)     # Segment is not a pytree
            dropped += sum(s.length for s in run) - merged.length
            copied_bytes += merged.words.size * 4
            for row in range(merged.length):
                store._by_id[int(merged.ids[row])] = (merged, row)
            if merged.length:       # an all-dead run just vanishes
                new_sealed.append(merged)
        store.sealed = new_sealed
        store._place()
        if runs:
            store.generation += 1
            # external ids survive a rewrite, so listeners (e.g. the
            # shadow reservoir) only need to know membership was churned
            store._notify("compact", None)
        reg = store.registry
        reg.counter("index.compactions").inc()
        reg.counter("index.compact_rows_dropped").inc(dropped)
        reg.counter("index.compact_bytes_copied").inc(copied_bytes)
        store._update_gauges()
        sp.set(runs=len(runs), rows_dropped=dropped)
    return {"runs": len(runs), "segments_before": before,
            "segments_after": len(store.sealed),
            "rows_dropped": dropped, "bytes_copied": copied_bytes}
