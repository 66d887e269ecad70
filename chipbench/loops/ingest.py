"""Closed-loop bulk ingest: a loader sends batches of embedding rows to
``AnnService.bulk_load`` back to back.

Traffic keys: ``batch_rows`` (rows per call), ``pool_rows`` (rows made in
set-up from the seed and held on the host, sent in turn), ``swap_rows``
(when the index holds this many rows it is replaced by a fresh one, so
device memory stays within one shard once ingest fills a shard inside
the window; at today's rate it does not), ``sample`` (stored
rows the reference checks), ``trace_seconds``.

A row is acknowledged once ``bulk_load`` has returned and its words are
on the device: the loader blocks on the store's words after each call.
The window lasts exactly ``--seconds``: ``ingest_rows_per_s`` is the
rows acknowledged by its close over its length. The call in progress at
the close runs to its end, and its rows are not counted, so a pause in
that call (a garbage collection of the id map, a seal) weighs by the
share of the window it takes, not by the whole pause.
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from chipbench import data, reference
from chipbench.loops import _search


def setup(ctx):
    cfg, tr = ctx.cfg, ctx.traffic
    sk = _search.sketcher(cfg, ctx.seed)
    ctx.mark("imports and device")
    pool = np.asarray(data.unit_rows(data.key(ctx.seed, data.POOL), 0,
                                     tr["pool_rows"], cfg["d"]))
    ctx.mark("row pool")
    # one segment filled and sealed, and one call into the next: every
    # program the window runs, swap included
    warm = _search.service(cfg, sk, cfg["segment_rows"])
    for g in range(cfg["segment_rows"] // tr["batch_rows"] + 1):
        warm.bulk_load(_batch(pool, tr["batch_rows"], g),
                       chunk_rows=cfg["ingest_chunk_rows"])
    jax.block_until_ready([s.words for s in warm.engine.store.segments()])
    ctx.mark("one segment and a seal")
    return {"sk": sk, "pool": pool}


def _batch(pool, rows: int, g: int):
    """Call ``g``'s rows: the pool's slices in turn."""
    lo = (g % (pool.shape[0] // rows)) * rows
    return pool[lo:lo + rows]


def window(state, ctx):
    cfg, tr = ctx.cfg, ctx.traffic
    sk, pool = state["sk"], state["pool"]
    rows, chunk = tr["batch_rows"], cfg["ingest_chunk_rows"]
    svc, calls, g, failed, swaps = None, [], 0, 0, 0

    def fresh(svc, calls):
        nonlocal failed
        if svc is not None:
            failed += len(calls) * rows - svc.engine.store.n_rows
        return _search.service(cfg, sk, cfg["segment_rows"]), []

    t0 = time.perf_counter()
    close = t0 + ctx.seconds
    svc, calls = fresh(None, [])
    acked = 0           # rows acknowledged by the close
    while True:
        if svc.engine.store.n_rows + rows > tr["swap_rows"]:
            with ctx.ann("bench.swap"):
                svc, calls = fresh(svc, calls)
            swaps += 1
        if ctx.trace:
            for _ in range(-(-rows // chunk)):
                ctx.record("encode", m=chunk, d=cfg["d"], k=cfg["k"],
                           w=svc.engine.store.n_words)
        with ctx.ann("bench.bulk_load"):
            svc.bulk_load(_batch(pool, rows, g), chunk_rows=chunk)
            jax.block_until_ready(
                [s.words for s in svc.engine.store.segments()])
        t = time.perf_counter()
        calls.append(g)
        g += 1
        if t > close:
            break
        acked = g * rows
        ctx.tick({"rows": acked})
    failed += len(calls) * rows - svc.engine.store.n_rows
    return {"attempted": g * rows, "failed": failed,
            "e2e": {"ingest_rows_per_s": acked / ctx.seconds},
            "notes": {"calls": g, "acked_rows": acked, "swaps": swaps,
                      "last_call_past_close_s": t - close},
            "svc": svc, "calls": calls}


def outputs(state, res, ctx):
    """The words stored for a seeded sample of the last index's rows,
    read back from its segments, and the pool rows they were sent as."""
    rows = ctx.traffic["batch_rows"]
    store, calls = res["svc"].engine.store, res["calls"]
    want = _search.sample(ctx.seed, len(calls) * rows, ctx.traffic["sample"])
    got = np.zeros((want.size, store.n_words), np.uint32)
    found = np.zeros(want.size, bool)
    for seg in store.segments():
        ids = seg.ids[:seg.length]
        pos = np.minimum(np.searchsorted(want, ids), want.size - 1)
        hit = want[pos] == ids
        if hit.any():
            got[pos[hit]] = np.asarray(jnp.take(
                seg.words, jnp.asarray(np.flatnonzero(hit)), axis=0))
            found[pos[hit]] = True
    src = np.stack([_batch(state["pool"], rows, calls[i // rows])[i % rows]
                    for i in want])
    return {"words": got, "found": found, "rows": src}


def check(ctx, out):
    """``field_mismatch``: codes of sampled acknowledged rows whose
    stored words differ from the reference's coding of the row sent
    (a row missing from the index counts all k)."""
    cfg = ctx.cfg
    r = reference.projection(data.sketch_seed(ctx.seed), cfg["d"],
                             cfg["k"], cfg["r_unit"])
    ref = np.asarray(reference.codes(jnp.asarray(out["rows"]), r, cfg["w"],
                                     cfg["precision"]))
    mism = (reference.unpack(out["words"], cfg["bits"], cfg["k"])
            != ref).sum(axis=1)
    mism = np.where(out["found"], mism, cfg["k"])
    return {"field_mismatch": (int(mism.sum()),
                               cfg["limits"]["field_mismatch"])}
