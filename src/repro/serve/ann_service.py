"""Microbatching front-end for the ANN engines (serving-layer component).

Mirrors ``serve.serving``'s split between jit'd device steps and a thin
host loop: individual queries arrive via ``submit`` (a ticket comes
back), ``flush`` pads the pending queue up to the next bucket size and
runs ONE batched engine search per bucket-shaped batch. Bucketed padding
keeps the jit cache to a handful of entries regardless of traffic shape —
``warmup`` pre-compiles every bucket so the first real query never pays
compile latency.

Two engine flavors plug in unchanged: the immutable ``ann.AnnEngine``
and the mutable ``index.MutableAnnEngine``. For mutable engines the
service exposes ``add``/``delete``/``upsert``/``compact`` endpoints that
interleave with queries.

Result cache: an LRU keyed on the query's *packed code words* (identical
vectors — and any vectors that code identically — share an entry) plus
the search knobs. Entries are valid for exactly one engine
``generation``: any index mutation bumps the generation and the next
flush drops the whole cache, so a cached hit is always bit-identical to
a fresh search.

Classification: attach a trained ``repro.learn.PackedLinearModel``
(``set_classifier``) and ``classify`` runs the same fused
project→code→pack front end as search (the engine's shared
``QueryCoder``), then the packed-linear forward kernel — one service,
two workloads over one set of codes.

Observability: every endpoint reports through a ``repro.obs``
``MetricsRegistry`` (per-service instance by default; inject a shared
one via the ``registry`` field) — latency histograms (``serve.flush_s``,
``serve.search_batch_s``, ``serve.classify_s``), ticket age from
``submit`` to result (``serve.ticket_age_s``), cache hit/miss/eviction/
invalidation and warmup-compile counters, and a padding-waste gauge.
The old ad-hoc ``stats`` dict survives as a read-only compat property
derived from the counters. ``flush``/``classify`` also open tracing
spans when a ``repro.obs.Tracer`` is installed.

Flight recorder: every endpoint additionally appends a structured
event (op, queue/start/sync timestamps, batch shape, cache hits, store
generation, outcome, trace id) to an always-on ``obs.FlightRecorder``
ring, and ``flush``/``classify`` run under a ``TailSampler`` request:
each gets a shallow span chain, and the full trace is retained when the
request lands in the slow tail — keyed by *deadline-relative lateness*
(oldest ticket age minus ``cfg.deadline_s``, so "slow" means late
against the SLO, not merely large) — errors, or is flagged by a quality
monitor. Retained requests pin exemplars (their trace id) onto the
``serve.flush_s`` histogram buckets, and when an ``IncidentManager`` is
attached (``incidents`` field, or just a directory string) endpoint
errors and drift alarms dump full incident bundles.

Closed-loop health: ``slo=True`` (or an injected ``obs.slo.SloEngine``)
registers default ``SloSpec``s per endpoint — latency against
``cfg.deadline_s`` over ``serve.flush_s``/``serve.classify_s``,
availability from the ``serve.*_errors`` counters, and a quality SLO
fed by shadow recall — and ticks the engine once per flush/classify.
Burn-rate alarms ride the same wiring as drift alarms (flag the
in-flight trace, dump an incident bundle carrying the SLO state);
``service.slo.health()`` is the admission-control verdict.
``resources=True`` attaches an ``obs.resources.ResourceMonitor``
(engine store bytes tracked, jit-recompile counter armed at the end of
``warmup`` via ``mark_steady`` — the never-recompile invariant becomes
a budgeted SLO). ``probe_search``/``probe_classify`` are the canary
endpoints ``obs.probe.CanaryProber`` replays known-answer rows through:
the real serving path (cache included) with telemetry segregated under
``serve.probe.*`` and the tail sampler and quality samplers suspended.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from contextlib import contextmanager
from dataclasses import dataclass, field
from types import MappingProxyType

import numpy as np
import jax.numpy as jnp

from repro.ann.engine import SearchConfig
from repro.kernels import ops as _ops
from repro.obs import (MetricsRegistry, TailSampler,
                       default_flight_recorder, span)

__all__ = ["AnnServiceConfig", "AnnService"]

#: shared no-op sampler for probe traffic — probes must never occupy
#: the retained-trace budget nor move the slow-tail threshold
_PROBE_SAMPLER = TailSampler(enabled=False)


@dataclass(frozen=True)
class AnnServiceConfig:
    """Static service knobs; one engine jit cache entry per bucket."""
    top_k: int = 10
    mode: str = "exact"            # exact | lsh
    min_bands: int = 1
    n_probes: int = 0
    buckets: tuple = (1, 8, 64, 256)   # padded batch shapes (ascending)
    cache_size: int = 256          # LRU result entries (0 disables)
    impl: str = "auto"
    scored: bool = False           # LUT-scored ranking (repro.rank)
    rerank_m: int = 0              # scored: coarse candidates (0 = auto)
    fused: bool = True             # single-pass fused scored kernel
    table_dtype: str = "auto"      # auto | f32 | bf16 | int8 (fused only)
    autotune_warmup: bool = False  # warmup also tunes kernel block sizes
    deadline_s: float = 0.050      # per-flush SLO; lateness keys the tail


@dataclass
class AnnService:
    """Queue + pad-to-bucket batching + result LRU over a shared engine;
    optionally also a classification endpoint over the same codes."""
    engine: object
    cfg: AnnServiceConfig = field(default_factory=AnnServiceConfig)
    classifier: object = None     # learn.PackedLinearModel (optional)
    registry: object = None       # obs.MetricsRegistry (own one if None)
    quality: object = None        # True | QualityConfig | QualityMonitors
    flight: object = None         # obs.FlightRecorder (global if None)
    sampler: object = None        # obs.TailSampler (own one if None)
    incidents: object = None      # obs.IncidentManager | directory str
    slo: object = None            # True | obs.slo.SloEngine
    resources: object = None      # True | obs.resources.ResourceMonitor

    def __post_init__(self):
        self._queue = []          # [(ticket, vector [D])]
        self._results = {}        # ticket -> (ids [top_k], rho [top_k])
        self._next_ticket = 0
        self._submit_ts = {}      # ticket -> submit wall-clock (ticket age)
        self._cache = OrderedDict()   # key -> (ids np, rho np)
        self._cache_gen = None
        if self.registry is None:
            self.registry = MetricsRegistry(enabled=True)
        reg = self.registry
        self._c_queries = reg.counter("serve.queries")
        self._c_batches = reg.counter("serve.batches")
        self._c_padded = reg.counter("serve.padded_rows")
        self._c_hits = reg.counter("serve.cache_hits")
        self._c_misses = reg.counter("serve.cache_misses")
        self._c_evict = reg.counter("serve.cache_evictions")
        self._c_inval = reg.counter("serve.cache_invalidations")
        self._c_warm = reg.counter("serve.warmup_compiles")
        self._c_classified = reg.counter("serve.classified_rows")
        self._c_flush_err = reg.counter("serve.flush_errors")
        self._c_classify_err = reg.counter("serve.classify_errors")
        self._h_flush = reg.histogram("serve.flush_s")
        self._h_batch = reg.histogram("serve.search_batch_s")
        self._h_age = reg.histogram("serve.ticket_age_s")
        self._h_classify = reg.histogram("serve.classify_s")
        self._g_pending = reg.gauge("serve.pending")
        self._g_waste = reg.gauge("serve.padding_waste")
        self._probing = False
        if self.flight is None:
            self.flight = default_flight_recorder()
        if self.sampler is None:
            self.sampler = TailSampler(registry=reg)
        if isinstance(self.incidents, str):
            from repro.obs import IncidentManager
            self.incidents = IncidentManager(
                self.incidents, flight=self.flight, sampler=self.sampler,
                registry=reg, generation_fn=lambda: getattr(
                    self.engine, "generation", 0))
        self._drift_flags = []    # series that alarmed since last request
        if self.quality is not None:
            from repro.obs.quality import QualityConfig, QualityMonitors
            if self.quality is True:
                self.quality = QualityConfig()
            if isinstance(self.quality, QualityConfig):
                self.quality = QualityMonitors(
                    self.engine.sketcher, self.quality, registry=reg)
            # the engine hook samples searches; mutable engines also
            # subscribe the shadow reservoir to store delete events
            if getattr(self.engine, "quality", None) is not self.quality:
                self.engine.attach_quality(self.quality)
            # drift alarms flag the in-flight request for trace
            # retention and (when wired) dump an incident bundle
            self.quality.on_drift(self._on_drift)
            if self.incidents is not None and \
                    getattr(self.incidents, "quality", None) is None:
                self.incidents.quality = self.quality
        if self.resources is True:
            from repro.obs.resources import ResourceMonitor
            self.resources = ResourceMonitor(registry=reg)
        if self.resources is not None:
            store = getattr(self.engine, "store", None)
            if store is not None and hasattr(store, "nbytes"):
                self.resources.track("engine.store", store)
        if self.slo is True:
            from repro.obs.slo import SloEngine
            self.slo = SloEngine(registry=reg)
        if self.slo is not None:
            from repro.obs.slo import SloSpec
            # default endpoint objectives: latency against the flush
            # deadline, availability from the error counters, quality
            # fed by shadow recall (floor 0.8) and probe verdicts
            if "search" not in self.slo.specs:
                self.slo.add(SloSpec(
                    "search", latency_hist="serve.flush_s",
                    latency_target_s=self.cfg.deadline_s,
                    error_counter="serve.flush_errors",
                    quality_min=0.8))
            if "classify" not in self.slo.specs:
                self.slo.add(SloSpec(
                    "classify", latency_hist="serve.classify_s",
                    latency_target_s=self.cfg.deadline_s,
                    error_counter="serve.classify_errors"))
            if self.resources is not None:
                self.slo.attach_resources(self.resources)
            # burn-rate alarms ride the drift wiring: flag the
            # in-flight trace for retention + dump an incident bundle
            self.slo.subscribe(self._on_drift)
            if self.incidents is not None and \
                    getattr(self.incidents, "slo", None) is None:
                self.incidents.slo = self.slo

    def _on_drift(self, series: str, value: float, detector):
        self._drift_flags.append(series)
        if self.incidents is not None:
            self.incidents.on_drift(series, value, detector)

    @property
    def stats(self):
        """Read-only view of the endpoint counters (compat shape: the
        pre-registry ad-hoc dict keys, plus the newer counters)."""
        return MappingProxyType({
            "queries": self._c_queries.value,
            "batches": self._c_batches.value,
            "padded_rows": self._c_padded.value,
            "cache_hits": self._c_hits.value,
            "cache_misses": self._c_misses.value,
            "cache_evictions": self._c_evict.value,
            "cache_invalidations": self._c_inval.value,
            "warmup_compiles": self._c_warm.value,
        })

    # -- request path --------------------------------------------------------
    def submit(self, x) -> int:
        """Enqueue one query vector [D]; returns a ticket for ``result``."""
        x = jnp.asarray(x)
        if x.ndim != 1:
            raise ValueError(f"submit takes a single vector, got {x.shape}")
        t = self._next_ticket
        self._next_ticket += 1
        self._queue.append((t, x))
        self._submit_ts[t] = time.perf_counter()
        self._g_pending.set(len(self._queue))
        return t

    def result(self, ticket: int):
        """(ids, rho) for a flushed ticket; KeyError if not flushed yet."""
        return self._results[ticket]

    def pending(self) -> int:
        return len(self._queue)

    # -- mutation endpoints (mutable engines only) ---------------------------
    def _mutable(self):
        if not getattr(self.engine, "mutable", False):
            raise TypeError("engine is immutable (ann.AnnEngine); build "
                            "the service over index.MutableAnnEngine for "
                            "add/delete/upsert")
        return self.engine

    def _mut_event(self, op: str, t0: float, batch: int = 0,
                   outcome: str = "ok"):
        """One flight event for a mutation endpoint (generation read
        *after* the mutation, so the event carries the new one)."""
        self.flight.record(op, t0, time.perf_counter(), batch=batch,
                           generation=getattr(self.engine,
                                              "generation", 0),
                           outcome=outcome)

    def add(self, x, ids=None):
        """Ingest vectors [m, D]; returns their external ids. The result
        cache invalidates on the next flush (generation bump)."""
        t0 = time.perf_counter()
        out = self._mutable().add(x, ids=ids)
        if self.quality is not None:
            self.quality.offer_rows(out, x)
        self._mut_event("serve.add", t0, batch=len(np.asarray(out)))
        return out

    def bulk_load(self, x, ids=None, chunk_rows: int = 2048):
        """Stream a whole corpus (dense [m, D] or ``encode.CsrMatrix``)
        into the index through the fused matrix-free ingest pipeline
        (``repro.encode``): chunked project→code→pack with only packed
        words written back, O(batch) tail appends. Returns the external
        ids int64 [m]; the result cache invalidates on the next flush.
        """
        t0 = time.perf_counter()
        out = self._mutable().ingest(x, ids=ids, chunk_rows=chunk_rows,
                                     impl=self.cfg.impl)
        if self.quality is not None:
            self.quality.offer_rows(out, x)
        self._mut_event("serve.bulk_load", t0, batch=len(np.asarray(out)))
        return out

    def delete(self, ids, strict: bool = True) -> int:
        """Tombstone external ids; the quality bundle's shadow reservoir
        (if attached) drops them via the store's delete listener."""
        t0 = time.perf_counter()
        n = self._mutable().delete(ids, strict=strict)
        self._mut_event("serve.delete", t0, batch=int(n))
        return n

    def upsert(self, ids, x):
        t0 = time.perf_counter()
        out = self._mutable().upsert(ids, x)
        if self.quality is not None:
            self.quality.offer_rows(out, x)
        self._mut_event("serve.upsert", t0, batch=len(np.asarray(out)))
        return out

    def compact(self, *args, **kwargs) -> dict:
        t0 = time.perf_counter()
        out = self._mutable().compact(*args, **kwargs)
        self._mut_event("serve.compact", t0,
                        batch=int(out.get("rows_dropped", 0)))
        return out

    # -- classification endpoint ---------------------------------------------
    def set_classifier(self, model) -> "AnnService":
        """Attach a trained ``learn.PackedLinearModel`` (k/bits must
        match the engine's store); returns self for chaining."""
        store = self.engine.store
        if (model.fspec.k, model.fspec.bits) != (store.k, store.bits):
            raise ValueError(
                f"classifier k/bits {(model.fspec.k, model.fspec.bits)} "
                f"!= store {(store.k, store.bits)}")
        self.classifier = model
        return self

    def classify(self, x):
        """Classify vectors x [m, D] -> (labels int [m], margins f32
        [C, m]) through the engine's shared fused query coder and the
        packed-linear forward kernel; requires ``set_classifier``.

        Batches are padded up to the service's bucket shapes (slices of
        at most the largest bucket), so classify traffic shares the
        search path's never-recompile property: one executable per
        bucket, whatever m arrives.
        """
        if self.classifier is None:
            raise TypeError("no classifier attached; call "
                            "set_classifier(model) first")
        x = jnp.asarray(x)
        if x.ndim != 2:
            raise ValueError(f"classify takes a batch [m, D], got {x.shape}")
        t0 = time.perf_counter()
        with self.sampler.request("classify", rows=int(x.shape[0])) as rq:
            with span("serve.classify", rows=int(x.shape[0])) as sp:
                try:
                    preds, margs = [], []
                    max_b = self.cfg.buckets[-1]
                    for lo in range(0, x.shape[0], max_b):
                        sub = x[lo:lo + max_b]
                        n = sub.shape[0]
                        b = self._bucket_for(n)
                        if b > n:
                            sub = jnp.pad(sub, ((0, b - n), (0, 0)))
                        codes = self.engine.encode_queries(
                            sub, impl=self.cfg.impl)
                        words = _ops.pack_codes(
                            codes, self.engine.store.bits,
                            impl=self.cfg.impl)
                        m = self.classifier.margins(
                            words, impl=self.cfg.impl)
                        preds.append(np.asarray(
                            self.classifier.predict_from_margins(m))[:n])
                        margs.append(np.asarray(sp.sync(m))[:, :n])
                    self._c_classified.inc(int(x.shape[0]))
                except Exception as e:
                    self._c_classify_err.inc()
                    if self.incidents is not None:
                        self.incidents.capture(
                            "error",
                            f"classify: {type(e).__name__}: {e}")
                    raise
        t1 = time.perf_counter()
        self._h_classify.observe(t1 - t0)
        self.flight.record("serve.classify", t0, t1,
                           batch=int(x.shape[0]),
                           generation=self._cache_gen or 0,
                           trace_id=rq.trace_id, synced=True)
        if rq.retained:
            self._h_classify.exemplar(t1 - t0, rq.trace_id)
        labels, margins = np.concatenate(preds), np.concatenate(margs, axis=1)
        qm = self.quality
        if qm is not None and qm.sample():
            qm.observe_margins(margins)     # calibration drift series
        if self.slo is not None:
            self.slo.tick()
        return labels, margins

    # -- batch execution -----------------------------------------------------
    def _bucket_for(self, n: int) -> int:
        for b in self.cfg.buckets:
            if n <= b:
                return b
        return self.cfg.buckets[-1]

    def _cache_key(self, word_row: np.ndarray):
        """Result-cache key: the query's packed code words + every knob
        that changes the search result (scored included — count-ranked
        and score-ranked results never alias)."""
        cfg = self.cfg
        return (word_row.tobytes(), cfg.top_k, cfg.mode, cfg.min_bands,
                cfg.n_probes, cfg.scored, cfg.rerank_m, cfg.fused,
                cfg.table_dtype)

    def _sync_cache_generation(self):
        gen = getattr(self.engine, "generation", 0)
        if gen != self._cache_gen:
            if self._cache_gen is not None and self._cache:
                self._c_inval.inc()
            self._cache.clear()
            self._cache_gen = gen

    def flush(self):
        """Run every pending query; returns {ticket: (ids, rho)}.

        Queries are taken in arrival order, in slices of at most the
        largest bucket; cache hits are served host-side and only misses
        are padded up to a bucket shape and searched.

        The whole flush runs as one tail-sampled request: its trace is
        retained when the oldest ticket finishes later than
        ``cfg.deadline_s`` past the current slow-quantile threshold,
        when it raises (also captured as an incident bundle when an
        ``IncidentManager`` is wired), or when a quality monitor
        flagged drift since the last request.
        """
        t_flush = time.perf_counter()
        with self.sampler.request("search",
                                  pending=len(self._queue)) as rq:
            with span("serve.flush", pending=len(self._queue)) as sp:
                try:
                    out = self._flush(sp, rq)
                except Exception as e:
                    self._c_flush_err.inc()
                    if self.incidents is not None:
                        self.incidents.capture(
                            "error", f"flush: {type(e).__name__}: {e}")
                    raise
            if self._drift_flags:
                for s in self._drift_flags:
                    rq.flag(s)
                self._drift_flags = []
        dur = time.perf_counter() - t_flush
        self._h_flush.observe(dur)
        if rq.retained:
            self._h_flush.exemplar(dur, rq.trace_id)
        self._g_pending.set(len(self._queue))
        if self.slo is not None:
            self.slo.tick()
        return out

    # -- canary-probe endpoints ----------------------------------------------
    @contextmanager
    def _probe_context(self):
        """Run one probe through the real endpoint code with its
        telemetry segregated: every per-request metric the endpoints
        touch is swapped for a ``probe.*`` twin, the tail sampler is
        replaced by a disabled one (probes never occupy the retained-
        trace budget or shift the slow-tail threshold), and quality
        sampling is suspended at both the service and the engine's
        collision hook (probes must not advance the seeded
        shadow/margin sampling streams or skew collision statistics —
        a replayed user workload still samples identically). The
        result cache and engine path are deliberately untouched: a
        probe exercises exactly what user traffic exercises, stale
        cache included."""
        reg = self.registry
        saved = (self._h_flush, self._h_batch, self._h_age,
                 self._h_classify, self._c_queries, self._c_hits,
                 self._c_misses, self._c_batches, self._c_padded,
                 self._c_classified, self._c_flush_err,
                 self._c_classify_err, self._g_waste, self.sampler,
                 self.quality)
        eng_quality = getattr(self.engine, "quality", None)
        self._h_flush = reg.histogram("serve.probe.flush_s")
        self._h_batch = reg.histogram("serve.probe.search_batch_s")
        self._h_age = reg.histogram("serve.probe.ticket_age_s")
        self._h_classify = reg.histogram("serve.probe.classify_s")
        self._c_queries = reg.counter("serve.probe.queries")
        self._c_hits = reg.counter("serve.probe.cache_hits")
        self._c_misses = reg.counter("serve.probe.cache_misses")
        self._c_batches = reg.counter("serve.probe.batches")
        self._c_padded = reg.counter("serve.probe.padded_rows")
        self._c_classified = reg.counter("serve.probe.classified_rows")
        self._c_flush_err = reg.counter("serve.probe.flush_errors")
        self._c_classify_err = reg.counter("serve.probe.classify_errors")
        self._g_waste = reg.gauge("serve.probe.padding_waste")
        self.sampler = _PROBE_SAMPLER
        self.quality = None
        if eng_quality is not None:      # engine-level collision hook
            self.engine.quality = None
        self._probing = True
        try:
            yield
        finally:
            (self._h_flush, self._h_batch, self._h_age,
             self._h_classify, self._c_queries, self._c_hits,
             self._c_misses, self._c_batches, self._c_padded,
             self._c_classified, self._c_flush_err,
             self._c_classify_err, self._g_waste, self.sampler,
             self.quality) = saved
            if eng_quality is not None:
                self.engine.quality = eng_quality
            self._probing = False

    def probe_search(self, x):
        """Known-answer canary search of ONE vector [D]; returns
        (ids, rho). The real submit→flush path runs — bucket padding,
        result cache, engine search — under ``_probe_context`` so the
        probe is invisible to user-facing metrics, the tail sampler,
        and the quality samplers (``obs.probe`` holds the prober that
        drives this and judges the answer)."""
        x = jnp.asarray(x)
        if x.ndim != 1:
            raise ValueError(f"probe_search takes one vector, "
                             f"got {x.shape}")
        saved_queue, self._queue = self._queue, []
        t0 = time.perf_counter()
        outcome = "error"
        t = None
        try:
            with self._probe_context():
                t = self.submit(x)
                out = self.flush()
            outcome = "ok"
            return out[t]
        finally:
            if t is not None:
                self._results.pop(t, None)
                self._submit_ts.pop(t, None)
            self._queue = saved_queue
            self._g_pending.set(len(self._queue))
            self.flight.record("serve.probe", t0, time.perf_counter(),
                               batch=1, generation=self._cache_gen or 0,
                               outcome=outcome)

    def probe_classify(self, x):
        """Canary classify of a batch [m, D] through the real
        ``classify`` path with probe-segregated telemetry; returns
        (labels, margins)."""
        t0 = time.perf_counter()
        outcome = "ok"
        try:
            with self._probe_context():
                return self.classify(x)
        except Exception:
            outcome = "error"
            raise
        finally:
            self.flight.record("serve.probe_classify", t0,
                               time.perf_counter(),
                               batch=int(np.asarray(x).shape[0]),
                               generation=self._cache_gen or 0,
                               outcome=outcome)

    def _flush(self, sp, rq=None):
        out = {}
        cfg = self.cfg
        self._sync_cache_generation()
        max_b = cfg.buckets[-1]
        max_age = 0.0
        trace_id = rq.trace_id if rq is not None else 0
        while self._queue:
            batch = self._queue[:max_b]
            self._queue = self._queue[max_b:]
            n = len(batch)
            # pad to the bucket BEFORE any device work, so every jit'd
            # stage (encode included) only ever sees bucket shapes
            b = self._bucket_for(n)
            x = jnp.stack([v for _, v in batch])
            if b > n:
                x = jnp.pad(x, ((0, b - n), (0, 0)))
            q_codes = self.engine.encode_queries(x, impl=cfg.impl)
            qm = self.quality
            if qm is not None and qm.sample():
                # budgeted shadow check of one real (unpadded) query:
                # exact-cosine ground truth vs the coded ranking over
                # the reservoir (obs.shadow)
                qi = int(qm.rng.integers(n))
                r = qm.shadow_check(batch[qi][1],
                                    self.engine.encode_queries,
                                    q_codes=q_codes[qi])
                if r is not None and self.slo is not None:
                    # shadow recall is the quality SLO's ground truth
                    self.slo.observe_quality("search", r)
            res = [None] * n
            miss = list(range(n))
            keys = None
            if cfg.cache_size:
                words = np.asarray(_ops.pack_codes(
                    q_codes, self.engine.store.bits, impl=cfg.impl))
                keys = [self._cache_key(words[i]) for i in range(n)]
                miss = []
                for i, key in enumerate(keys):
                    hit = self._cache.get(key)
                    if hit is not None:
                        self._cache.move_to_end(key)
                        res[i] = hit
                    else:
                        miss.append(i)
            if miss:
                if len(miss) == n:
                    sub, b2 = q_codes, b          # already bucket-shaped
                else:
                    # gather with a bucket-shaped index list (row 0
                    # repeated as filler) so the gather itself only ever
                    # compiles at bucket shapes
                    b2 = self._bucket_for(len(miss))
                    idx = miss + [0] * (b2 - len(miss))
                    sub = q_codes[jnp.asarray(idx)]
                t_batch = time.perf_counter()
                ids, rho = self.engine.search_codes(
                    sub, SearchConfig(top_k=cfg.top_k, mode=cfg.mode,
                                      min_bands=cfg.min_bands,
                                      n_probes=cfg.n_probes, chunk_q=b2,
                                      impl=cfg.impl, scored=cfg.scored,
                                      rerank_m=cfg.rerank_m,
                                      fused=cfg.fused,
                                      table_dtype=cfg.table_dtype))
                # host transfer is the device sync for this batch's
                # timing (np.asarray blocks on the result buffers)
                ids, rho = np.asarray(sp.sync(ids)), np.asarray(rho)
                t_done = time.perf_counter()
                self._h_batch.observe(t_done - t_batch)
                self.flight.record(
                    "serve.search", t_batch, t_done,
                    t_queue=min(self._submit_ts.get(t, t_batch)
                                for t, _ in batch),
                    batch=b2, cache_hits=n - len(miss),
                    generation=self._cache_gen or 0,
                    trace_id=trace_id, synced=True)
                for j, i in enumerate(miss):
                    res[i] = (ids[j], rho[j])
                    if cfg.cache_size:
                        self._cache[keys[i]] = res[i]
                        while len(self._cache) > cfg.cache_size:
                            self._cache.popitem(last=False)
                            self._c_evict.inc()
                self._c_batches.inc()
                self._c_padded.inc(b2 - len(miss))
                self._g_waste.set((b2 - len(miss)) / b2)
            now = time.perf_counter()
            for (t, _), r in zip(batch, res):
                self._results[t] = r
                out[t] = r
                t0 = self._submit_ts.pop(t, None)
                if t0 is not None:
                    age = now - t0
                    self._h_age.observe(age)
                    if age > max_age:
                        max_age = age
            self._c_queries.inc(n)
            self._c_hits.inc(n - len(miss))
            self._c_misses.inc(len(miss))
        if rq is not None:
            # deadline-relative lateness keys the slow-tail reservoir:
            # a flush is "slow" when its oldest ticket beat the SLO by
            # less than its peers, not merely when it was large
            rq.set_key(max_age - cfg.deadline_s)
        return out

    def warmup(self, d: int):
        """Pre-compile every bucket shape (cold-start insurance).

        With ``autotune_warmup=True`` this first runs the block-size
        sweep for the search kernel families at the engine's corpus
        shape (``kernels.autotune.tune_search_ops``) so the bucket
        compiles below already pick up tuned configs; on CPU backends
        the sweep is a safe no-op (autotune refuses to measure there).
        """
        cfg = self.cfg
        if cfg.autotune_warmup:
            from repro.kernels import autotune as _autotune
            store = self.engine.store
            dtype = {"auto": "float32", "f32": "float32",
                     "bf16": "bfloat16", "int8": "int8"}.get(
                         cfg.table_dtype, "float32")
            # CodeStore carries a words array; SegmentLogStore carries
            # the packed width directly
            n_rows = int(getattr(store, "n", 0)
                         or getattr(store, "n_rows", 0) or 0)
            w = (store.words.shape[-1] if hasattr(store, "words")
                 else store.n_words)
            _autotune.tune_search_ops(
                n=max(n_rows, 1), w=w, bits=store.bits,
                k=self.engine.sketcher.cfg.k, q=cfg.buckets[-1],
                top_k=cfg.top_k, table_dtype=dtype)
        with span("serve.warmup", buckets=len(cfg.buckets)) as sp:
            for b in cfg.buckets:
                sp.sync(self.engine.search(
                    jnp.zeros((b, d)), cfg.top_k, mode=cfg.mode,
                    min_bands=cfg.min_bands,
                    n_probes=cfg.n_probes, chunk_q=b,
                    impl=cfg.impl, scored=cfg.scored,
                    rerank_m=cfg.rerank_m, fused=cfg.fused,
                    table_dtype=cfg.table_dtype))
                self._c_warm.inc()
        # warmup compiles are free; anything after this burns the
        # never-recompile budget (obs.resources / obs.slo)
        if self.resources is not None:
            self.resources.mark()
        if self.slo is not None:
            self.slo.mark_steady()
        return self
