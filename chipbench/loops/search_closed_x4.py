"""Closed-loop served search over one multi-chip host's share of the
corpus: ``search_closed``'s clients and window, over a ``MutableAnnEngine``
whose segment log is placed across the cell's chips (segment s on chip
s mod chips; the merge on the first chip).

Traffic keys as ``search_closed``'s. The window is ``search_closed``'s
own; ``_search.flush_calls`` logs one scan call per live segment of each
flush slice, on every chip, for the roofline. The check is
``_search.check``'s scored comparison, with the reference's corpus blocks
spread over the same chips (``reference_x4``).
"""
from __future__ import annotations

import numpy as np
import jax

from chipbench import data, reference, reference_x4
from chipbench.loops import _search, search_closed

window, outputs = search_closed.window, search_closed.outputs


def chips(ctx) -> list:
    """The devices the cell places its segments on."""
    return jax.devices()[:ctx.workload["chips"]]


def service(cfg: dict, sk, rows: int, devices):
    """An empty ``AnnService`` over a ``MutableAnnEngine`` placed over
    ``devices``, whose segments hold ``rows`` rows each."""
    from repro.index.engine import MutableAnnEngine
    from repro.serve import AnnService, AnnServiceConfig
    engine = MutableAnnEngine(sk, band_spec=None, tail_rows=rows,
                              devices=devices)
    sc = dict(cfg["service"])
    sc["buckets"] = tuple(sc["buckets"])
    return AnnService(engine, AnnServiceConfig(**sc))


def setup(ctx):
    cfg, seed, tr = ctx.cfg, ctx.seed, ctx.traffic
    c, chunk = tr["clients"], cfg["build_chunk_rows"]
    svc = service(cfg, _search.sketcher(cfg, seed), cfg["segment_rows"],
                  chips(ctx))
    ctx.mark("imports and device")
    for i in range(cfg["rows"] // chunk):
        svc.bulk_load(data.corpus_chunk(seed, i, chunk, cfg["d"]),
                      chunk_rows=cfg["ingest_chunk_rows"])
    ctx.mark("corpus")
    q = _search.queries(cfg, seed, c * (tr["pool_flushes"] + 1), tr["noise"])
    ctx.mark("queries")
    for row in q[-c:]:                  # the one bucket the window uses
        svc.submit(row)
    svc.flush()
    ctx.mark("served path")
    return {"svc": svc, "q": q[:-c]}


def check(ctx, out):
    """``score_gap`` and ``rho_gap`` as ``_search.check`` computes them
    for scored search."""
    cfg = ctx.cfg
    lim = cfg["limits"]
    qc = _search.query_codes(cfg, ctx.seed, out["queries"])
    ids = np.asarray(out["ids"])
    bad = _search._invalid(ids, cfg["rows"])
    v, _, mine = reference_x4.search_scored(
        cfg, ctx.seed, cfg["rows"] // cfg["build_chunk_rows"], qc,
        np.where(bad, -1, ids), chips(ctx))
    gap = np.where(bad | ~np.isfinite(mine), np.inf, np.abs(v - mine))
    rho = reference.rho_from_scores(v, cfg)
    return {"score_gap": (float(gap.max()), lim["score_gap"]),
            "rho_gap": (float(np.abs(rho - out["rho"]).max()),
                        lim["rho_gap"])}
