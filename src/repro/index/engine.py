"""Mutable ANN engine: batched search over the segment log.

The serving twin of ``ann.AnnEngine`` for a corpus that changes under
traffic: same query path (fused project→code→pack via the shared
``QueryCoder``, same ``SearchConfig`` knobs, same chunking), but the
corpus side is a ``SegmentLogStore``. Each segment is searched with the
*masked* streaming top-k kernel (tombstones skipped on device), local
rows are swapped for external ids, and the per-segment lists are fused
by ``ann.engine.merge_topk`` — segments are ordered by log position, so
the merged tie-break is identical to one search over a fresh immutable
store of the live rows. That equivalence is the subsystem's contract:
mutate however you like, search never tells the difference.

LSH mode mirrors ``AnnEngine``'s banded retrieval per segment: coarse
matching-band scores against the segment's resident band hashes, the
validity mask folded into the candidate filter, full packed collision
re-rank, then the same cross-segment merge.

Scored search (``scored=True``) also runs per segment. The default
path is the single-pass fused masked kernel
(``kernels.fused_scored``): each segment is streamed once, the top-m
live candidates by collision count are selected and LUT-scored
entirely in-VMEM, and the cross-segment merge compares calibrated
float scores — the same merge, float sentinel instead of -1. With
``fused=False`` the legacy two-stage path runs instead (masked coarse
top-m, then the LUT re-rank kernel over gathered candidates); both
paths return bit-identical results — ``tests/test_kernel_conformance``
holds them to it.

Placement (``devices=...``, see ``index.segment_log``): each chunk puts
the query's words and tables once on every chip that holds a live
segment (span ``search.place``), dispatches each segment's kernel on its
own chip in log order with no host sync, so the chips run at once, then
moves the per-segment (vals, ids) to the merge chip ``devices[0]``
(span ``search.gather``) and merges them there in log order: the same
tie-break as one chip's log. ``last_shard_bytes`` counts what crossed
chips.
"""
from __future__ import annotations

import time as _time

import numpy as np
import jax
import jax.numpy as jnp

from repro.ann.bands import BandSpec, probe_hashes
from repro.ann.engine import (QueryCoder, SearchConfig, _coarse_band_scores,
                              lut_rerank_stage, merge_topk,
                              resolve_query_tables, rho_scored,
                              run_chunked)
from repro.rank.tables import RankTables, build_rank_tables
from repro.core import packing as _packing
from repro.core.sketch import CodedRandomProjection
from repro.index.compaction import CompactionPolicy, compact
from repro.index.segment_log import SegmentLogStore, device_id
from repro.index.snapshot import restore_index, save_index
from repro.kernels import ops as _ops
from repro.kernels import ref as _ref
from repro.obs import default_flight_recorder, deep_tracing_active, span

__all__ = ["MutableAnnEngine"]


class MutableAnnEngine:
    """In-place mutable index: add/delete/upsert/compact + batched search.

    Returned ids are *external* item ids (stable across upserts, seals,
    compaction and restarts), not store rows. ``generation`` increments
    on every mutation — the serving layer keys result-cache validity on
    it.
    """

    mutable = True

    def __init__(self, sketcher: CodedRandomProjection, *,
                 band_spec: BandSpec = BandSpec(), tail_rows: int = 1024,
                 impl: str = "auto", store: SegmentLogStore = None,
                 rank_tables: RankTables = None, devices=None):
        self.sketcher = sketcher
        self._rank_tables = rank_tables
        if store is None:
            store = SegmentLogStore(sketcher.cfg.k, sketcher.spec.bits,
                                    band_spec=band_spec,
                                    tail_rows=tail_rows, impl=impl,
                                    devices=devices)
        elif devices is not None:
            raise ValueError("devices place a new store; a given store "
                             "keeps its own placement")
        if (store.k, store.bits) != (sketcher.cfg.k, sketcher.spec.bits):
            raise ValueError(
                f"store k/bits {(store.k, store.bits)} != sketcher "
                f"{(sketcher.cfg.k, sketcher.spec.bits)}")
        self.store = store
        self.band_spec = store.band_spec
        self._coder = QueryCoder(sketcher)
        self.quality = None       # obs.quality.QualityMonitors, if attached
        # one int32 [4] device array per fused scored kernel call of the
        # last ``search_codes``: the 8-row chunks it LUT-scored, all of
        # them, the histogram bins it visited, all of them (the oracle
        # path appends none)
        self.last_kernel_counters = []
        # bytes the last ``search_codes`` moved to another chip: the
        # query put on each segment's chip, the results gathered to merge
        self.last_shard_bytes = [0, 0]

    # -- mutation ------------------------------------------------------------
    @property
    def generation(self) -> int:
        """Monotone mutation counter (result-cache invalidation key)."""
        return self.store.generation

    @property
    def n(self) -> int:
        """Live (non-tombstoned) rows."""
        return self.store.n_live

    def add(self, x, ids=None) -> np.ndarray:
        """Encode vectors x float [m, D] and append (O(batch) donated
        tail write, never O(corpus)); returns external ids int64 [m].
        Encoding runs through the shared ``repro.encode`` encoder — the
        same numerics as queries and ``ingest``."""
        return self.store.add_codes(self.encoder.encode_codes(x), ids=ids)

    def add_codes(self, codes, ids=None) -> np.ndarray:
        """Append pre-encoded int codes [m, k]; returns external ids
        int64 [m] (see ``SegmentLogStore.add_codes`` for id rules)."""
        return self.store.add_codes(codes, ids=ids)

    def add_words(self, words, ids=None) -> np.ndarray:
        """Append already-packed uint32 rows [m, W] (fused-ingest path);
        returns external ids int64 [m]."""
        return self.store.add_words(words, ids=ids)

    @property
    def encoder(self):
        """The shared ``repro.encode.StreamingEncoder`` behind the query
        coder — also the bulk-ingest encoder (one R cache, one seed)."""
        return self._coder._encoder

    def ingest(self, x, ids=None, *, chunk_rows: int = 2048,
               impl: str = "auto") -> np.ndarray:
        """Bulk-load raw vectors (dense [m, D] or ``encode.CsrMatrix``)
        through the fused project→code→pack pipeline straight into the
        segment log — no [m, k] f32/int32 intermediates, O(batch) tail
        writes; returns the external ids int64 [m]."""
        from repro.encode.pipeline import IngestPipeline
        return IngestPipeline(self.encoder, self.store,
                              chunk_rows=chunk_rows, impl=impl).ingest(
                                  x, ids=ids)

    def delete(self, ids, strict: bool = True) -> int:
        """Tombstone external ids (1-bit mask write, zero recompiles);
        returns rows killed. Unknown ids raise iff ``strict``."""
        return self.store.delete(ids, strict=strict)

    def upsert(self, ids, x) -> np.ndarray:
        """Replace-or-insert vectors x float [m, D] under stable
        external ids int [m]; returns the ids (same shared-encoder
        numerics as ``add``/``ingest``/queries)."""
        return self.store.upsert_codes(ids, self.encoder.encode_codes(x))

    def upsert_codes(self, ids, codes) -> np.ndarray:
        """Replace-or-insert pre-encoded int codes [m, k] under stable
        external ids int [m]; returns the ids."""
        return self.store.upsert_codes(ids, codes)

    def compact(self, policy: CompactionPolicy = CompactionPolicy()) -> dict:
        """Size-tiered compaction (drops tombstones, preserves result
        order bit-exactly); returns the compaction report dict."""
        return compact(self.store, policy)

    # -- durability ----------------------------------------------------------
    def save(self, directory: str, step: int, keep: int = 3) -> str:
        """Atomic snapshot of the store under ``directory`` at ``step``
        (keeping ``keep`` newest); returns the snapshot path."""
        return save_index(self.store, directory, step, keep=keep)

    @classmethod
    def restore(cls, sketcher: CodedRandomProjection, directory: str,
                step: int = None, devices=None) -> "MutableAnnEngine":
        """Engine over a restored store (latest snapshot, or ``step``),
        placed over ``devices`` when given."""
        return cls(sketcher, store=restore_index(directory, step, devices))

    # -- search --------------------------------------------------------------
    @property
    def rank_tables(self) -> RankTables:
        """LUT scoring tables for scored search, built lazily from the
        sketcher's (scheme, k) on first use (pass ``rank_tables`` to
        ``__init__`` to override, e.g. for bf16-quantized tables)."""
        if self._rank_tables is None:
            self._rank_tables = build_rank_tables(self.sketcher)
        return self._rank_tables

    def encode_queries(self, x, impl: str = "auto"):
        """x float [Q, D] -> int32 codes [Q, k] (fused proj+code)."""
        return self._coder.encode(x, impl=impl)

    # -- quality audit hooks -------------------------------------------------
    def attach_quality(self, monitors) -> "MutableAnnEngine":
        """Attach an ``obs.quality.QualityMonitors`` bundle: every search
        gets a budgeted chance (its ``sample_rate``) of feeding one
        query-candidate batch to the collision monitor, and the bundle's
        shadow reservoir subscribes to the store's delete events so its
        ground truth stays tombstone-aware. Returns self."""
        self.quality = monitors
        self.store.add_listener(monitors.on_store_event)
        return self

    def codes_for_ids(self, ids):
        """int32 codes [m, k] of live *external* ids (the small per-id
        gather the quality audit re-scores against)."""
        return self.store.take_codes(ids)

    def search(self, queries, top_k: int = 10, *, mode: str = "exact",
               min_bands: int = 1, n_probes: int = 0, chunk_q: int = 256,
               impl: str = "auto", scored: bool = False,
               rerank_m: int = 0, fused: bool = True,
               table_dtype: str = "auto"):
        """queries float [Q, D] -> (ids int32 [Q, top_k], rho_hat
        float32 [Q, top_k]); ids are external item ids, -1 marks empty
        slots. ``scored=True`` LUT-scores each segment's coarse top-m
        (m = ``rerank_m``, 0 = auto) — single-pass fused masked kernel
        by default, two-stage rerank with ``fused=False`` — and returns
        rho_hat calibrated from the non-linear scores. ``table_dtype``
        picks the query-table storage (see ``SearchConfig``)."""
        cfg = SearchConfig(top_k=top_k, mode=mode, min_bands=min_bands,
                           n_probes=n_probes, chunk_q=chunk_q, impl=impl,
                           scored=scored, rerank_m=rerank_m, fused=fused,
                           table_dtype=table_dtype)
        return self.search_codes(self.encode_queries(queries, impl=impl),
                                 cfg)

    def search_codes(self, q_codes, cfg: SearchConfig):
        """Search pre-encoded queries [Q, k] across all segments."""
        if cfg.mode not in ("exact", "lsh"):
            raise ValueError(f"unknown mode {cfg.mode!r}")
        if cfg.mode == "lsh" and self.band_spec is None:
            raise ValueError("store built without band_spec: lsh "
                             "retrieval unavailable")
        if cfg.table_dtype == "int8" and not cfg.use_fused():
            raise ValueError("table_dtype='int8' requires the fused "
                             "scored path (scored=True, fused=True, "
                             "mode='exact')")
        q = q_codes.shape[0]
        self.last_kernel_counters = []
        self.last_shard_bytes = [0, 0]
        if q == 0 or self.store.n_live == 0:
            return (jnp.full((q, cfg.top_k), -1, jnp.int32),
                    jnp.full((q, cfg.top_k), -1.0, jnp.float32))
        t0 = _time.perf_counter()
        with span("engine.search", queries=int(q)):
            out = run_chunked(q_codes, cfg, self._search_chunk)
        default_flight_recorder().record(
            "index.search", t0, _time.perf_counter(), batch=int(q),
            generation=self.generation, outcome=cfg.mode,
            synced=deep_tracing_active())
        if self.quality is not None:
            self.quality.observe_search(q_codes, out[0], self.codes_for_ids)
        return out

    def _search_chunk(self, q_codes, cfg: SearchConfig):
        """One padded query chunk across all segments: per-segment
        (masked) top-k or scored two-stage, then the cross-segment
        merge. Returns (ids int32 [c, top_k], rho float32 [c, top_k])."""
        k = self.sketcher.cfg.k
        bits = self.store.bits
        q_words = _ops.pack_codes(q_codes, bits, impl=cfg.impl)
        qh = (probe_hashes(q_codes, self.band_spec, cfg.n_probes)
              if cfg.mode == "lsh" else None)
        # the per-query LUTs are segment-independent: build once per
        # chunk, not once per segment (this loop runs eagerly)
        fused = cfg.scored and cfg.use_fused()
        q_tables = scales = None
        if fused:
            q_tables, scales = resolve_query_tables(
                self.rank_tables, q_codes, cfg.table_dtype)
        elif cfg.scored:
            q_tables = self.rank_tables.query_tables(q_codes)
        live = [(i, seg) for i, seg in enumerate(self.store.segments())
                if seg.live]
        query = (q_words, q_tables, scales, qh)
        placed = (self._place_query(query, live)
                  if self.store.devices is not None else None)
        vals_l, ids_l = [], []
        # the span syncs below only block under a *deep* tracer
        # (profiling); with no tracer, or a shallow per-request
        # RequestTrace, the eager segment loop keeps its async pipeline
        for i, seg in live:
            qw, qt, qs, qp = query if placed is None else placed[seg.device]
            if fused:
                m = cfg.resolve_m(seg.cap)
                with span("search.fused", segment=i, rows=seg.cap,
                          m=m, top_k=cfg.top_k,
                          device=device_id(seg.device)) as sp:
                    vals, rows = _ops.fused_scored_topk_masked(
                        qw, qt, seg.words, seg.valid_dev(),
                        bits, k, m, cfg.top_k, scales=qs,
                        impl=cfg.impl, counters=self.last_kernel_counters)
                    sp.sync(vals)
                ext = jnp.take(seg.ids_dev(),
                               jnp.clip(rows, 0, seg.cap - 1), axis=0)
                ids_l.append(jnp.where(rows < 0, -1, ext))
                vals_l.append(vals)
                continue
            top = cfg.resolve_m(seg.cap) if cfg.scored else cfg.top_k
            with span("search.coarse", mode=cfg.mode, segment=i,
                      rows=seg.cap, device=device_id(seg.device)) as sp:
                if cfg.mode == "exact":
                    vals, rows = _ops.packed_topk_masked(
                        qw, seg.words, seg.valid_dev(), bits, k,
                        top, impl=cfg.impl)
                else:
                    counts = _ops.packed_collision_counts(
                        qw, seg.words, bits, k, impl=cfg.impl)
                    coarse = _coarse_band_scores(qp, seg.hashes)
                    live_rows = _packing.unpack_bitmask(seg.valid_dev(),
                                                        seg.cap)
                    counts = jnp.where(live_rows[None, :]
                                       & (coarse >= cfg.min_bands),
                                       counts, -1)
                    vals, rows = _ref.topk_stable_ref(counts, top)
                sp.sync(rows)
            if cfg.scored:
                with span("search.rerank", segment=i,
                          top_k=cfg.top_k) as sp:
                    rows, vals = lut_rerank_stage(
                        self.rank_tables, q_codes, rows, seg.words,
                        cfg.top_k, impl=cfg.impl, q_tables=qt)
                    sp.sync(vals)
            ext = jnp.take(seg.ids_dev(),
                           jnp.clip(rows, 0, seg.cap - 1), axis=0)
            ids_l.append(jnp.where(rows < 0, -1, ext))
            vals_l.append(vals)
        if placed is not None:
            vals_l, ids_l = self._gather_results(vals_l, ids_l)
        vals, ids = merge_topk(vals_l, ids_l, cfg.top_k)
        if cfg.scored:
            return ids, rho_scored(self.rank_tables, ids, vals)
        return ids, self._rho(vals)

    def _place_query(self, query, live):
        """The chunk's query arrays put once on each chip that holds a
        live segment -> {device: query}; bytes that cross chips count
        into ``last_shard_bytes[0]``."""
        chips = list(dict.fromkeys(seg.device for _, seg in live))
        with span("search.place", chips=len(chips)):
            placed = {}
            for dev in chips:
                placed[dev] = jax.device_put(query, dev)
                self.last_shard_bytes[0] += _bytes_off(query, dev)
        return placed

    def _gather_results(self, vals_l, ids_l):
        """Every segment's (vals, ids) moved to the merge chip
        ``devices[0]``, in log order; bytes that cross chips count into
        ``last_shard_bytes[1]``."""
        merge = self.store.devices[0]
        moved = sum(merge not in v.devices() for v in vals_l)
        with span("search.gather", segments=moved):
            self.last_shard_bytes[1] += _bytes_off((vals_l, ids_l), merge)
            return jax.device_put((vals_l, ids_l), merge)

    def _rho(self, counts):
        """Collision counts -> rho_hat (paper estimator); empty slots
        (count < 0) surface as rho = -1."""
        rho = self.sketcher._estimator(counts / self.sketcher.cfg.k)
        return jnp.where(counts < 0, -1.0, rho)


def _bytes_off(tree, device) -> int:
    """Bytes of the arrays in ``tree`` that are not on ``device``."""
    return sum(x.nbytes for x in jax.tree.leaves(tree)
               if device not in x.devices())
