"""Shared by the search loops: the served index built from the seed,
the seeded queries, and the comparison with the plain reference."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from chipbench import data, reference


def sketcher(cfg: dict, seed: int):
    from repro.core.sketch import CodedRandomProjection, SketchConfig
    return CodedRandomProjection(SketchConfig(
        k=cfg["k"], scheme=cfg["scheme"], w=cfg["w"], r_unit=cfg["r_unit"],
        seed=data.sketch_seed(seed)), d=cfg["d"])


def service(cfg: dict, sk, rows: int, service_cfg: dict = None):
    """An empty ``AnnService`` over a ``MutableAnnEngine`` whose
    segments hold ``rows`` rows each."""
    from repro.index.engine import MutableAnnEngine
    from repro.serve import AnnService, AnnServiceConfig
    engine = MutableAnnEngine(sk, band_spec=None, tail_rows=rows)
    sc = dict(service_cfg or cfg["service"])
    sc["buckets"] = tuple(sc["buckets"])
    return AnnService(engine, AnnServiceConfig(**sc))


def build(ctx, n_queries: int, service_cfg: dict = None):
    """The served corpus, loaded through ``AnnService.bulk_load`` chunk
    by chunk, and ``n_queries`` queries (see ``queries``)."""
    cfg, seed = ctx.cfg, ctx.seed
    chunk = cfg["build_chunk_rows"]
    svc = service(cfg, sketcher(cfg, seed), cfg["segment_rows"],
                  service_cfg)
    ctx.mark("imports and device")
    for i in range(cfg["rows"] // chunk):
        svc.bulk_load(data.corpus_chunk(seed, i, chunk, cfg["d"]),
                      chunk_rows=cfg["ingest_chunk_rows"])
    ctx.mark("corpus")
    q = queries(cfg, seed, n_queries, ctx.traffic["noise"])
    ctx.mark("queries")
    return svc, q


def queries(cfg: dict, seed: int, n: int, noise: float) -> np.ndarray:
    """n distinct queries, host float32 [n, d]: each a distinct corpus
    row (regenerated from the seed) moved by noise and renormalized."""
    chunk, d = cfg["build_chunk_rows"], cfg["d"]
    src = data.pick_sources(seed, cfg["rows"], n)
    rows = np.zeros((n, d), np.float32)
    for i in np.unique(src // chunk):
        mine = np.flatnonzero(src // chunk == i)
        rows[mine] = np.asarray(jnp.take(
            data.corpus_chunk(seed, int(i), chunk, d),
            jnp.asarray(src[mine] % chunk), axis=0))
    return np.asarray(data.near(data.key(seed, data.NOISE),
                                jnp.asarray(rows), noise, d))


def warm_sizes(ctx, svc, sizes) -> None:
    """Compile what a flush of each pending count in ``sizes`` runs
    outside the engine (stacking and padding the batch), on a
    one-segment service of the same configuration, so the window never
    compiles whatever batch the arrivals make."""
    cfg = ctx.cfg
    small = service(cfg, svc.engine.sketcher, 1024,
                    {**cfg["service"], "cache_size": svc.cfg.cache_size})
    small.bulk_load(data.unit_rows(data.key(ctx.seed, data.POOL), 99,
                                   1024, cfg["d"]), chunk_rows=1024)
    x = np.asarray(data.unit_rows(data.key(ctx.seed, data.POOL), 98,
                                  max(sizes), cfg["d"]))
    for n in sizes:
        for row in x[:n]:
            small.submit(row)
        small.flush()


def sample(seed: int, answered: int, size: int) -> np.ndarray:
    """Indices of the answered requests the reference checks."""
    r = data.rng(seed, 77)
    return np.sort(r.permutation(answered)[:min(size, answered)])


def query_codes(cfg: dict, seed: int, q):
    r = reference.projection(data.sketch_seed(seed), cfg["d"], cfg["k"],
                             cfg["r_unit"])
    return reference.codes(jnp.asarray(q), r, cfg["w"], cfg["precision"])


def check(ctx, out, n_chunks: int = None) -> dict:
    """The numbers compared with the reference for served search:
    ``out`` holds the sampled queries and what the window answered
    (ids [S, top_k], rho [S, top_k]). Unscored search: ``count_gap``,
    the widest gap between the reference's j-th best collision count
    and the count of the id the program put j-th, and ``rho_gap``, the
    widest gap between the program's rho and the reference's at the same
    rank. Scored search: ``score_gap`` and ``rho_gap`` the same way in
    score units."""
    cfg = ctx.cfg
    lim = cfg["limits"]
    n_chunks = n_chunks or cfg["rows"] // cfg["build_chunk_rows"]
    qc = query_codes(cfg, ctx.seed, out["queries"])
    ids = np.asarray(out["ids"])
    bad = _invalid(ids, cfg["rows"])
    if cfg["service"]["scored"]:
        v, _, mine = reference.search_scored(cfg, ctx.seed, n_chunks, qc,
                                             np.where(bad, -1, ids))
        gap = np.where(bad | ~np.isfinite(mine), np.inf, np.abs(v - mine))
        rho = reference.rho_from_scores(v, cfg)
        return {"score_gap": (float(gap.max()), lim["score_gap"]),
                "rho_gap": (float(np.abs(rho - out["rho"]).max()),
                            lim["rho_gap"])}
    v, _, mine = reference.search_exact(cfg, ctx.seed, n_chunks, qc,
                                        np.where(bad, -1, ids))
    gap = np.where(bad | (mine < 0), cfg["k"], np.abs(v - mine))
    rho = reference.rho_from_counts(v, cfg["k"], cfg["w"],
                                    cfg["estimator"]["grid"],
                                    cfg["estimator"]["rho_max"])
    return {"count_gap": (int(gap.max()), lim["count_gap"]),
            "rho_gap": (float(np.abs(rho - out["rho"]).max()),
                        lim["rho_gap"])}


def _invalid(ids, n: int):
    """Ids that are empty, outside the corpus, or repeated in a row."""
    bad = (ids < 0) | (ids >= n)
    s = np.sort(ids, axis=1)
    dup = np.zeros_like(bad)
    dup[:, 1:] = s[:, 1:] == s[:, :-1]
    return bad | dup.any(axis=1, keepdims=True)


def flush_calls(ctx, svc, pending: int) -> None:
    """Log the scan kernel calls of one flush of ``pending`` queries for
    the roofline: per slice of at most the largest bucket, one call per
    live segment at the slice's bucket."""
    if not ctx.trace:
        return
    cfg, store = svc.cfg, svc.engine.store
    top = cfg.buckets[-1]
    sizes = [top] * (pending // top) + ([pending % top] if pending % top
                                        else [])
    kernel = "scan_scored" if cfg.scored else "scan_exact"
    for n in sizes:
        b = next(x for x in cfg.buckets if n <= x)
        for seg in store.segments():
            if seg.live:
                ctx.record(kernel, q=b, n=seg.cap, w=store.n_words,
                           k=store.k, bits=store.bits, top_k=cfg.top_k,
                           m=cfg.rerank_m or ctx.cfg["scoring"]["rerank_m"])
