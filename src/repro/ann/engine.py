"""Batched device-resident ANN search over packed codes.

The serving-side payoff of the paper's coding schemes (and of the
follow-ups 1403.8144 / 1602.06577): queries are fused-projected to b-bit
codes, bit-packed, and matched against a ``CodeStore`` without the codes
ever existing as int32 in HBM. Two candidate modes:

``exact``   — brute-force: streaming packed-collision top-k over the whole
              corpus (``kernels.packed_collision``; jnp oracle off-TPU).
``lsh``     — banded candidates: batched multi-probe band-hash matching
              (``ann.bands``) scores every corpus row by matching-band
              count; only rows sharing >= ``min_bands`` buckets with the
              query are eligible (classic LSH retrieval semantics), and
              eligible rows are re-ranked by full packed collision count.
              Packed counts are so cheap (32/b codes per uint32 XOR) that
              re-ranking is a masked brute pass rather than a gather —
              the candidate *set* is exact, never truncated to a fixed C,
              and grows monotonically with ``n_probes``.

Both modes also run **two-stage scored** (``scored=True``): the coarse
pass above selects top-``rerank_m`` candidates by collision count, then
a fused LUT kernel re-ranks them with the non-linear per-code-pair
scores of ``repro.rank`` (contingency-table log-likelihood ratios, the
1602.06577 estimator family) and returns calibrated rho_hat from the
scores. Collision counts only see the table's diagonal, so equal counts
hide real similarity differences; the re-rank breaks exactly those ties
and recovers recall the coarse pass leaves on the floor.

Both modes process queries in fixed-size chunks (padded to one shape, so
each mode compiles exactly twice: chunk shape + remainder-free path) and
return (ids [Q, top_k], rho_hat [Q, top_k]) with rho_hat from the paper's
collision estimator (table inversion of P(rho), or the LUT calibration
curve when scored). ``search_sharded`` runs the exact mode under
``shard_map`` with the corpus row-sharded across a mesh axis, merging
per-shard top-k by all-gather + re-top-k.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.ann.bands import BandSpec, band_hashes, probe_hashes
from repro.ann.store import CodeStore
from repro.core import packing as _packing
from repro.core.sketch import CodedRandomProjection
from repro.kernels import ops as _ops
from repro.kernels import ref as _ref
from repro.obs import default_flight_recorder, deep_tracing_active, span
from repro.rank.tables import RankTables, build_rank_tables

__all__ = ["SearchConfig", "AnnEngine", "QueryCoder", "merge_topk",
           "run_chunked", "lut_rerank_stage", "rho_scored",
           "resolve_query_tables"]


@dataclass(frozen=True)
class SearchConfig:
    """Static knobs of one search variant (one jit cache entry each)."""
    top_k: int = 10
    mode: str = "exact"          # exact | lsh
    min_bands: int = 1           # lsh: matching bands required to be a candidate
    n_probes: int = 0            # lsh: multi-probe expansions per band
    chunk_q: int = 256           # query rows per device step
    impl: str = "auto"           # kernel dispatch (see kernels.ops)
    scored: bool = False         # scored search: LUT scores, calibrated rho
    rerank_m: int = 0            # scored: coarse candidates (0 = auto)
    fused: bool = True           # scored exact: single-pass kernel (False =
    #                              the literal two-stage coarse -> re-rank)
    table_dtype: str = "auto"    # LUT storage: auto | f32 | bf16 | int8

    def resolve_m(self, n: int) -> int:
        """Coarse candidate count for one part with ``n`` rows: the
        configured ``rerank_m`` (default 4*top_k, floor 64), never below
        ``top_k`` and never above ``n`` (all static => one jit entry)."""
        m = self.rerank_m or max(64, 4 * self.top_k)
        return max(1, min(max(m, self.top_k), n))

    def use_fused(self) -> bool:
        """Whether this config takes the single-pass fused scored kernel:
        scored exact search only (lsh's band filter runs in the coarse
        stage, so lsh scored stays two-stage)."""
        return self.scored and self.fused and self.mode == "exact"


def resolve_query_tables(tables: RankTables, q_codes, table_dtype: str):
    """Build per-query LUTs in the configured storage dtype ->
    (q_tables [Q, F*P], scales [Q, W] or None).

    ``auto`` takes the table bundle's own dtype (f32, or bf16 after
    ``quantize``); ``f32``/``bf16`` force it; ``int8`` returns
    power-of-two-scaled int8 tables (``RankTables.query_tables_int8``),
    which only the fused scored kernel accepts.
    """
    if table_dtype == "int8":
        return tables.query_tables_int8(q_codes)
    named = {"auto": None, "f32": jnp.float32, "bf16": jnp.bfloat16}
    if table_dtype not in named:
        raise ValueError(f"unknown table_dtype {table_dtype!r}")
    return tables.query_tables(q_codes, dtype=named[table_dtype]), None


class QueryCoder:
    """Fused query encoder shared by the immutable engine and the mutable
    segment-log engine (``repro.index``): a thin facade over
    ``repro.encode.StreamingEncoder`` — fused proj+code kernel over the
    cached R below the residency cap, matrix-free unit streaming above
    it, so a D = 3.2M index never materializes [D, k] for queries
    either."""

    def __init__(self, sketcher: CodedRandomProjection):
        self.sketcher = sketcher
        self._encoder = sketcher.stream_encoder()   # shared per-sketcher

    def r_matrix(self):
        """Materialized projection [D, k] (cached), regenerated from the
        seed unit by unit.  Raises above the encoder's residency cap —
        large-D callers must stream (``encode`` does, transparently)."""
        return self._encoder.r_matrix()

    def encode(self, x, impl: str = "auto"):
        """x [Q, D] (dense or ``encode.CsrMatrix``) -> int32 codes
        [Q, k]: fused proj+code kernel when R is resident, streaming
        projection + scheme encode otherwise."""
        return self._encoder.encode_codes(x, impl=impl)

    def encode_packed(self, x, impl: str = "auto"):
        """x [Q, D] (dense or ``encode.CsrMatrix``) -> packed uint32
        [Q, W] via the fused project→code→pack ingest path."""
        return self._encoder.encode_packed(x, impl=impl)


def merge_topk(vals_list, ids_list, top_k: int):
    """Merge per-part (segment/shard) top-k lists into a global top-k.

    vals_list: per-part values, each int32 collision counts or float32
    LUT scores [Q, k_part]; ids_list: matching int32 ids [Q, k_part]
    (-1 = empty slot). Returns (vals [Q, top_k], ids int32 [Q, top_k]).

    Tie-break order: parts are concatenated in list order and
    ``lax.top_k`` is stable, so equal values resolve to the earliest
    part and, within a part, to the part's own list order (the kernels
    emit ties lowest-row-first). With parts ordered by row offset this
    reproduces the single-store tie-break exactly. Empty slots keep ids
    of -1: the sentinel value is -1 for integer counts and -inf for
    float scores (real float scores may be negative).
    """
    cat_v = jnp.concatenate(vals_list, axis=1)
    cat_i = jnp.concatenate(ids_list, axis=1)
    best_v, pos = jax.lax.top_k(cat_v, top_k)
    best_i = jnp.take_along_axis(cat_i, pos, axis=1)
    if jnp.issubdtype(cat_v.dtype, jnp.floating):
        empty = jnp.isneginf(best_v)
    else:
        empty = best_v < 0
    return best_v, jnp.where(empty, -1, best_i)


def run_chunked(q_codes, cfg: SearchConfig, chunk_fn):
    """Shared query chunking: pad Q up to a power-of-two chunk (jit cache
    stays <= log2(chunk_q) shapes however callers vary Q), run
    ``chunk_fn(q_codes[lo:hi], cfg)`` per chunk, unpad."""
    q = q_codes.shape[0]
    chunk = min(cfg.chunk_q, 1 << (q - 1).bit_length())
    cfg = replace(cfg, chunk_q=chunk)
    pad = (-q) % chunk
    if pad:
        q_codes = jnp.pad(q_codes, ((0, pad), (0, 0)))
    ids, rho = [], []
    for lo in range(0, q + pad, chunk):
        i, r = chunk_fn(q_codes[lo:lo + chunk], cfg)
        ids.append(i)
        rho.append(r)
    return jnp.concatenate(ids)[:q], jnp.concatenate(rho)[:q]


def lut_rerank_stage(tables: RankTables, q_codes, cand_ids, words_src,
                     top_k: int, impl: str = "auto", q_tables=None):
    """Second stage of a two-stage scored search (shared by the
    immutable, mutable and sharded paths).

    q_codes int32 [c, k]; cand_ids int32 [c, M] rows into ``words_src``
    uint32 [n, W] from a coarse pass (-1 = empty slot); returns
    (rows int32 [c, top_k] into words_src, -1 empty; scores f32
    [c, top_k], -inf empty). Gathers candidate rows, builds the
    query-specialized LUTs (pass prebuilt ``q_tables`` [c, F*P] to
    reuse them across calls, e.g. per-segment loops) and runs the fused
    re-rank kernel; fully jittable (one XLA gather + one kernel call).
    """
    n = words_src.shape[0]
    cand = jnp.take(words_src, jnp.clip(cand_ids, 0, n - 1), axis=0)
    if q_tables is None:
        q_tables = tables.query_tables(q_codes)
    scores, pos = _ops.packed_lut_rerank(q_tables, cand, cand_ids >= 0,
                                         tables.bits, top_k, impl=impl)
    rows = jnp.take_along_axis(cand_ids,
                               jnp.clip(pos, 0, cand_ids.shape[1] - 1),
                               axis=1)
    return jnp.where(pos < 0, -1, rows), scores


def rho_scored(tables: RankTables, ids, scores):
    """LUT scores [...] -> calibrated rho_hat float32 [...] via the
    tables' inversion curve; empty slots (id < 0) surface as rho = -1
    (the scored twin of the engines' count-based ``_rho``)."""
    rho = tables.rho_from_scores(scores)
    return jnp.where(ids < 0, -1.0, rho)


def _packed_counts_rowwise(q_words, cand_words, bits: int, k: int):
    """q_words [c, W] vs per-query candidates [c, C, W] -> int32 [c, C]."""
    w = q_words.shape[-1]
    mism = jnp.zeros(cand_words.shape[:-1], jnp.int32)
    for j in range(w):
        xor = jnp.bitwise_xor(q_words[:, None, j], cand_words[..., j])
        mism = mism + _packing.mismatch_count_words(xor, bits).astype(jnp.int32)
    return k - mism


def _coarse_band_scores(q_probe_hashes, db_hashes):
    """Matching-band counts: [c, P, L] vs [N, L] -> int32 [c, N].

    A band matches when *any* probe hits its bucket; looping the small
    static (P, L) axes keeps temporaries at [c, N].
    """
    c, p_n, l_n = q_probe_hashes.shape
    score = jnp.zeros((c, db_hashes.shape[0]), jnp.int32)
    for l in range(l_n):
        hit = jnp.zeros((c, db_hashes.shape[0]), bool)
        for p in range(p_n):
            hit = hit | (q_probe_hashes[:, p, l][:, None]
                         == db_hashes[None, :, l])
        score = score + hit.astype(jnp.int32)
    return score


class AnnEngine:
    """Immutable search engine: sketcher + packed corpus + band hashes."""

    def __init__(self, sketcher: CodedRandomProjection, store: CodeStore,
                 band_spec: BandSpec = BandSpec(), db_band_hashes=None,
                 rank_tables: RankTables = None):
        self.sketcher = sketcher
        self.store = store
        self.band_spec = band_spec.validate(sketcher.cfg.k)
        if db_band_hashes is None:
            db_band_hashes = band_hashes(store.unpack(), band_spec)
        self.db_band_hashes = db_band_hashes      # uint32 [n, L]
        self._coder = QueryCoder(sketcher)
        self._rank_tables = rank_tables
        self._search_fns = {}
        self._stage_fns = {}      # cfg -> (jit coarse, jit rerank)
        self.quality = None       # obs.quality.QualityMonitors, if attached

    # -- construction / ingestion -------------------------------------------
    @classmethod
    def build(cls, sketcher: CodedRandomProjection, corpus,
              band_spec: BandSpec = BandSpec(), impl: str = "auto"):
        """Index a corpus [n, D]: fused project+code, pack, band-hash —
        through the sketcher's shared ``repro.encode`` encoder, the
        same numerics queries use."""
        codes = sketcher.stream_encoder().encode_codes(corpus, impl=impl)
        return cls.from_codes(sketcher, codes, band_spec, impl=impl)

    @classmethod
    def from_codes(cls, sketcher: CodedRandomProjection, codes,
                   band_spec: BandSpec = BandSpec(), impl: str = "auto"):
        """Index pre-encoded int32 codes [n, k]: pack + band-hash."""
        store = CodeStore.from_codes(codes, sketcher.cfg.k,
                                     sketcher.spec.bits, impl=impl)
        return cls(sketcher, store, band_spec,
                   db_band_hashes=band_hashes(codes, band_spec))

    def add(self, x, impl: str = "auto") -> "AnnEngine":
        """New engine with corpus rows appended (ids continue from n);
        encoded through the shared query coder's fused path."""
        codes = self._coder.encode(x, impl=impl)
        store = self.store.add(codes, impl=impl)
        hashes = jnp.concatenate(
            [self.db_band_hashes, band_hashes(codes, self.band_spec)])
        new = AnnEngine(self.sketcher, store, self.band_spec,
                        db_band_hashes=hashes,
                        rank_tables=self._rank_tables)
        new.quality = self.quality
        return new

    @property
    def n(self) -> int:
        """Corpus rows resident in the store."""
        return self.store.n

    @property
    def rank_tables(self) -> RankTables:
        """LUT scoring tables for scored search, built lazily from the
        sketcher's (scheme, k) on first use (pass ``rank_tables`` to
        ``__init__`` to override, e.g. for bf16-quantized tables)."""
        if self._rank_tables is None:
            self._rank_tables = build_rank_tables(self.sketcher)
        return self._rank_tables

    # -- query encoding ------------------------------------------------------
    def _r_matrix(self):
        return self._coder.r_matrix()

    def encode_queries(self, x, impl: str = "auto"):
        """x [Q, D] -> int32 codes [Q, k] via the fused proj+code kernel."""
        return self._coder.encode(x, impl=impl)

    # -- quality audit hooks -------------------------------------------------
    def attach_quality(self, monitors) -> "AnnEngine":
        """Attach an ``obs.quality.QualityMonitors`` bundle: every search
        gets a budgeted chance (its ``sample_rate``) of feeding one
        query-candidate batch to the collision monitor. Returns self."""
        self.quality = monitors
        return self

    def codes_for_ids(self, ids):
        """int32 codes [m, k] of store rows ``ids`` (row positions) —
        the small gather the quality audit re-scores against."""
        words = self.store.take(jnp.asarray(ids, jnp.int32))
        return _packing.unpack_codes(words, self.sketcher.spec.bits,
                                     self.sketcher.cfg.k)

    # -- search --------------------------------------------------------------
    def search(self, queries, top_k: int = 10, *, mode: str = "exact",
               min_bands: int = 1, n_probes: int = 0,
               chunk_q: int = 256, impl: str = "auto",
               scored: bool = False, rerank_m: int = 0,
               fused: bool = True, table_dtype: str = "auto"):
        """queries float [Q, D] -> (ids int32 [Q, top_k], rho_hat
        float32 [Q, top_k]).

        ids of -1 mark empty slots (top_k exceeding corpus/candidates).
        ``scored=True`` runs the two-stage path — coarse collision top-m
        (m = ``rerank_m``, 0 = auto) then fused LUT re-rank — and
        returns rho_hat calibrated from the non-linear scores.
        """
        cfg = SearchConfig(top_k=top_k, mode=mode, min_bands=min_bands,
                           n_probes=n_probes, chunk_q=chunk_q, impl=impl,
                           scored=scored, rerank_m=rerank_m, fused=fused,
                           table_dtype=table_dtype)
        return self.search_codes(self.encode_queries(queries, impl=impl), cfg)

    def search_codes(self, q_codes, cfg: SearchConfig):
        """Search pre-encoded queries [Q, k] (chunked, padded to one shape).

        When a *deep* ``repro.obs.Tracer`` is installed (profiling),
        every chunk runs under device-synced spans — two-stage scored
        searches as a ``search.coarse`` / ``search.rerank`` pair (the
        two stages jit separately at a chunk boundary; same kernels,
        same results), so a trace attributes coarse and re-rank wall
        time honestly. Under a shallow per-request trace
        (``obs.RequestTrace``) the chunks keep their async fast path —
        one submission-timed ``search.chunks`` span carries the trace
        id instead, and a flight-recorder event marks the call.
        """
        if cfg.mode not in ("exact", "lsh"):
            raise ValueError(f"unknown mode {cfg.mode!r}")
        if cfg.table_dtype == "int8" and not cfg.use_fused():
            raise ValueError("int8 tables require the fused scored exact "
                             "path (scored=True, fused=True, mode='exact')")
        q = q_codes.shape[0]
        if q == 0 or self.store.n == 0:
            return (jnp.full((q, cfg.top_k), -1, jnp.int32),
                    jnp.full((q, cfg.top_k), -1.0, jnp.float32))
        t0 = time.perf_counter()
        if deep_tracing_active():
            out = run_chunked(q_codes, cfg, self._traced_chunk)
        else:
            with span("search.chunks", sync=False, mode=cfg.mode,
                      q=int(q), scored=cfg.scored):
                out = run_chunked(
                    q_codes, cfg,
                    lambda chunk, c: self._chunk_fn(c)(chunk))
        default_flight_recorder().record(
            "ann.search", t0, time.perf_counter(), batch=int(q),
            outcome=cfg.mode, synced=deep_tracing_active())
        if self.quality is not None:
            self.quality.observe_search(q_codes, out[0], self.codes_for_ids)
        return out

    def _chunk_fn(self, cfg: SearchConfig):
        """jit'd one-chunk search; cached per SearchConfig (warm cache)."""
        fn = self._search_fns.get(cfg)
        if fn is None:
            if cfg.scored:
                self.rank_tables        # host-side build, outside the trace
            body = (self._exact_chunk if cfg.mode == "exact"
                    else self._lsh_chunk)
            fn = jax.jit(functools.partial(body, cfg=cfg))
            self._search_fns[cfg] = fn
        return fn

    def _stage_fn_pair(self, cfg: SearchConfig):
        """jit'd (coarse, rerank) stage pair for span-split scored
        search; cached per SearchConfig like ``_chunk_fn``."""
        fns = self._stage_fns.get(cfg)
        if fns is None:
            self.rank_tables            # host-side build, outside the trace
            body = (self._exact_coarse if cfg.mode == "exact"
                    else self._lsh_coarse)
            coarse = jax.jit(functools.partial(body, cfg=cfg))
            rerank = jax.jit(lambda qc, ids: self._rerank(qc, ids, cfg))
            fns = self._stage_fns[cfg] = (coarse, rerank)
        return fns

    def _traced_chunk(self, chunk, cfg: SearchConfig):
        """One chunk under spans (tracer installed). Non-scored chunks
        get one ``search.chunk`` span; scored chunks split into
        device-synced ``search.coarse`` + ``search.rerank``."""
        if not cfg.scored:
            with span("search.chunk", mode=cfg.mode,
                      q=int(chunk.shape[0])) as sp:
                out = sp.sync(self._chunk_fn(cfg)(chunk))
            return out
        if cfg.use_fused():
            with span("search.fused", mode=cfg.mode,
                      q=int(chunk.shape[0]),
                      m=cfg.resolve_m(self.store.n),
                      top_k=cfg.top_k) as sp:
                out = sp.sync(self._chunk_fn(cfg)(chunk))
            return out
        coarse, rerank = self._stage_fn_pair(cfg)
        with span("search.coarse", mode=cfg.mode,
                  q=int(chunk.shape[0]),
                  m=cfg.resolve_m(self.store.n)) as sp:
            _, cand_ids = sp.sync(coarse(chunk))
        with span("search.rerank", top_k=cfg.top_k) as sp:
            out = sp.sync(rerank(chunk, cand_ids))
        return out

    def _rho(self, counts):
        """Collision counts -> rho_hat via the paper's estimator; empty
        slots (count < 0) surface as rho = -1."""
        k = self.sketcher.cfg.k
        rho = self.sketcher._estimator(counts / k)
        return jnp.where(counts < 0, -1.0, rho)

    def _rerank(self, q_codes, cand_ids, cfg: SearchConfig):
        """Coarse candidate rows -> (ids, rho) by fused LUT re-rank."""
        ids, scores = lut_rerank_stage(self.rank_tables, q_codes, cand_ids,
                                       self.store.words, cfg.top_k,
                                       impl=cfg.impl)
        return ids, rho_scored(self.rank_tables, ids, scores)

    def _exact_coarse(self, q_codes, *, cfg: SearchConfig):
        """Coarse pass of one exact chunk -> (vals, ids) at top-m (scored)
        or top-k (counts-only)."""
        q_words = _ops.pack_codes(q_codes, self.store.bits, impl=cfg.impl)
        top = cfg.resolve_m(self.store.n) if cfg.scored else cfg.top_k
        vals, ids = _ops.packed_topk(
            q_words, self.store.words, self.store.bits, self.sketcher.cfg.k,
            top, impl=cfg.impl)
        return vals, jnp.where(vals < 0, -1, ids)

    def _fused_chunk(self, q_codes, *, cfg: SearchConfig):
        """One scored exact chunk through the single-pass fused kernel:
        coarse top-m selection and LUT re-rank in one corpus stream —
        bit-identical results to the two-stage pair wherever LUT scores
        don\'t tie across different collision counts."""
        q_words = _ops.pack_codes(q_codes, self.store.bits, impl=cfg.impl)
        q_tables, scales = resolve_query_tables(self.rank_tables, q_codes,
                                                cfg.table_dtype)
        scores, ids = _ops.fused_scored_topk(
            q_words, q_tables, self.store.words, self.store.bits,
            self.sketcher.cfg.k, cfg.resolve_m(self.store.n), cfg.top_k,
            scales=scales, impl=cfg.impl)
        return ids, rho_scored(self.rank_tables, ids, scores)

    def _exact_chunk(self, q_codes, *, cfg: SearchConfig):
        if cfg.use_fused():
            return self._fused_chunk(q_codes, cfg=cfg)
        vals, ids = self._exact_coarse(q_codes, cfg=cfg)
        if cfg.scored:
            return self._rerank(q_codes, ids, cfg)
        return ids, self._rho(vals)

    def _lsh_coarse(self, q_codes, *, cfg: SearchConfig):
        """Coarse pass of one lsh chunk -> (vals, ids), band-filtered."""
        q_words = _ops.pack_codes(q_codes, self.store.bits, impl=cfg.impl)
        qh = probe_hashes(q_codes, self.band_spec, cfg.n_probes)
        coarse = _coarse_band_scores(qh, self.db_band_hashes)
        counts = _ops.packed_collision_counts(
            q_words, self.store.words, self.store.bits, self.sketcher.cfg.k,
            impl=cfg.impl)
        # non-candidates (too few matching bands) are unretrievable
        counts = jnp.where(coarse >= cfg.min_bands, counts, -1)
        top = cfg.resolve_m(self.store.n) if cfg.scored else cfg.top_k
        return _ref.topk_stable_ref(counts, top)

    def _lsh_chunk(self, q_codes, *, cfg: SearchConfig):
        vals, ids = self._lsh_coarse(q_codes, cfg=cfg)
        if cfg.scored:
            return self._rerank(q_codes, ids, cfg)
        return ids, self._rho(vals)

    # -- candidate introspection (compat wrapper + tests) --------------------
    def band_match_counts(self, q_codes, n_probes: int = 0):
        """[Q, k] codes -> int32 [Q, n] matching-band counts (coarse
        scores; a row is a candidate iff its count > 0). Monotone
        non-decreasing in ``n_probes`` (prefix-nested probes)."""
        qh = probe_hashes(q_codes, self.band_spec, n_probes)
        return _coarse_band_scores(qh, self.db_band_hashes)

    def rerank(self, q_codes, cand_ids):
        """Full packed collision counts of one query row's candidate list
        -> (counts [c], rho_hat [c])."""
        q_words = _ops.pack_codes(q_codes[None, :], self.store.bits,
                                  impl="ref")
        counts = _packed_counts_rowwise(
            q_words, self.store.take(jnp.asarray(cand_ids))[None, ...],
            self.store.bits, self.sketcher.cfg.k)[0]
        return counts, self._rho(counts)

    # -- multi-device path ---------------------------------------------------
    def search_sharded(self, queries, mesh: Mesh, axis: str = "data",
                       top_k: int = 10, impl: str = "auto",
                       scored: bool = False, rerank_m: int = 0,
                       fused: bool = True, table_dtype: str = "auto"):
        """Exact search with the corpus row-sharded over ``mesh[axis]``.

        queries float [Q, D] -> (ids int32 [Q, top_k], rho_hat float32
        [Q, top_k]). Each shard computes a local streaming top-k over
        its rows (local ids offset to global by the shard index), then
        the per-shard lists are all-gathered and re-top-k'd — the
        classic distributed top-k merge; every step stays on device.
        With ``scored=True`` each shard additionally LUT-scores its
        local coarse top-m before the merge (single-pass fused kernel
        by default, two-stage rerank with ``fused=False``), so the
        cross-shard merge compares calibrated scores, not counts.
        Query tables are built once on host side and replicated;
        ``table_dtype`` selects their storage (see ``SearchConfig``).
        """
        store = self.store.shard(mesh, axis)
        q_codes = self.encode_queries(queries, impl=impl)
        q_words = _ops.pack_codes(q_codes, store.bits, impl=impl)
        k = self.sketcher.cfg.k
        bits = store.bits
        n_local = store.n // mesh.shape[axis]
        tables = self.rank_tables if scored else None
        cfg = SearchConfig(top_k=top_k, scored=scored, rerank_m=rerank_m,
                           fused=fused, table_dtype=table_dtype)
        if cfg.table_dtype == "int8" and not cfg.use_fused():
            raise ValueError("table_dtype='int8' requires the fused "
                             "scored path (scored=True, fused=True)")

        def merge_gathered(vals, ids, offset):
            ids = jnp.where(ids < 0, -1, ids + offset)
            vg = jax.lax.all_gather(vals, axis)       # [n_sh, Q, top_k]
            ig = jax.lax.all_gather(ids, axis)
            vg = jnp.moveaxis(vg, 0, 1).reshape(vals.shape[0], -1)
            ig = jnp.moveaxis(ig, 0, 1).reshape(vals.shape[0], -1)
            best, pos = jax.lax.top_k(vg, top_k)
            return best, jnp.take_along_axis(ig, pos, axis=1)

        def local(qw, dbw):
            vals, ids = _ops.packed_topk(qw, dbw, bits, k, top_k, impl=impl)
            return merge_gathered(vals, ids,
                                  jax.lax.axis_index(axis) * dbw.shape[0])

        def local_scored(qw, qc, dbw):
            m = cfg.resolve_m(n_local)
            cvals, cids = _ops.packed_topk(qw, dbw, bits, k, m, impl=impl)
            cids = jnp.where(cvals < 0, -1, cids)
            rows, scores = lut_rerank_stage(tables, qc, cids, dbw, top_k,
                                            impl=impl)
            return merge_gathered(scores, rows,
                                  jax.lax.axis_index(axis) * dbw.shape[0])

        def local_fused(qw, tabs, dbw, scl=None):
            m = cfg.resolve_m(n_local)
            scores, rows = _ops.fused_scored_topk(
                qw, tabs, dbw, bits, k, m, top_k, scales=scl, impl=impl)
            return merge_gathered(scores, rows,
                                  jax.lax.axis_index(axis) * dbw.shape[0])

        if scored and cfg.use_fused():
            q_tables, scales = resolve_query_tables(tables, q_codes,
                                                    cfg.table_dtype)
            rep = P(None, None)
            if scales is None:
                fn = jax.shard_map(local_fused, mesh=mesh,
                                   in_specs=(rep, rep, P(axis, None)),
                                   out_specs=(rep, rep), check_vma=False)
                scores, ids = jax.jit(fn)(q_words, q_tables, store.words)
            else:
                fn = jax.shard_map(local_fused, mesh=mesh,
                                   in_specs=(rep, rep, P(axis, None), rep),
                                   out_specs=(rep, rep), check_vma=False)
                scores, ids = jax.jit(fn)(q_words, q_tables, store.words,
                                          scales)
            ids = jnp.where(jnp.isneginf(scores), -1, ids)
            return ids, rho_scored(tables, ids, scores)
        if scored:
            fn = jax.shard_map(local_scored, mesh=mesh,
                               in_specs=(P(None, None), P(None, None),
                                         P(axis, None)),
                               out_specs=(P(None, None), P(None, None)),
                               check_vma=False)
            scores, ids = jax.jit(fn)(q_words, q_codes, store.words)
            ids = jnp.where(jnp.isneginf(scores), -1, ids)
            return ids, rho_scored(tables, ids, scores)
        fn = jax.shard_map(local, mesh=mesh,
                           in_specs=(P(None, None), P(axis, None)),
                           out_specs=(P(None, None), P(None, None)),
                           check_vma=False)
        vals, ids = jax.jit(fn)(q_words, store.words)
        return jnp.where(vals < 0, -1, ids), self._rho(vals)
