"""repro.obs: histogram bucket math and percentile bounds, span
nesting/exception safety and the sync-boundary invariant, disabled-mode
no-op metrics, kernel-stat byte models vs the kernels/ref.py oracle
shapes, exporters, the instrumented serving/ingest/index layers, and
the committed full-cycle trace artifact (TRACE_obs_cycle.json)."""
import json
import math
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.ann import BandSpec
from repro.core import packing as PK
from repro.core.sketch import CodedRandomProjection, SketchConfig
from repro.index import MutableAnnEngine
from repro.kernels import ops as _ops
from repro.launch.roofline import V5E
from repro.obs import (KernelStats, MetricsRegistry, Tracer,
                       default_registry, no_tracing, set_default_registry,
                       set_kernel_stats, snapshot, span, to_prometheus,
                       tracing_active)
from repro.obs.kernelstats import model
from repro.obs.registry import (NULL_COUNTER, NULL_GAUGE, NULL_HISTOGRAM,
                                HistogramSpec)
from repro.obs.trace import _AnnotationSpan
from repro.serve.ann_service import AnnService, AnnServiceConfig

D, K = 16, 16
BAND = BandSpec(n_tables=4, band_width=4)


def _crp():
    return CodedRandomProjection(SketchConfig(k=K, scheme="2bit", w=0.75),
                                 D)


# -- histogram bucket math ----------------------------------------------------

def test_histogram_bucket_containment():
    spec = HistogramSpec()
    rng = np.random.default_rng(0)
    vals = np.exp(rng.uniform(np.log(2e-6), np.log(500.0), size=500))
    for v in vals:
        i = spec.bucket_index(float(v))
        lo, hi = spec.bucket_bounds(i)
        assert lo <= v <= hi * (1 + 1e-12), (v, lo, hi)


def test_histogram_bucket_index_monotone_and_clamped():
    spec = HistogramSpec()
    vals = np.exp(np.linspace(np.log(1e-9), np.log(1e9), 200))
    idx = [spec.bucket_index(float(v)) for v in vals]
    assert idx == sorted(idx)
    assert idx[0] == 0 and idx[-1] == spec.n_buckets - 1
    assert spec.bucket_bounds(0)[0] == 0.0        # underflow absorbed


def test_histogram_percentile_bounds_bracket_order_stat():
    """percentile_bounds(q) brackets the ceil(q*n)-th smallest value."""
    reg = MetricsRegistry()
    h = reg.histogram("t")
    rng = np.random.default_rng(1)
    vals = np.exp(rng.uniform(np.log(1e-5), np.log(10.0), size=1000))
    for v in vals:
        h.observe(float(v))
    s = np.sort(vals)
    for q in (0.5, 0.95, 0.99):
        lo, hi = h.percentile_bounds(q)
        want = s[math.ceil(q * len(s)) - 1]
        assert lo <= want <= hi * (1 + 1e-12), (q, want, lo, hi)
        # one-bucket tightness: the bracket is a single growth factor
        assert hi / max(lo, h.spec.lo) <= h.spec.growth * (1 + 1e-12)
        assert h.percentile(q) == hi


def test_histogram_summary_and_exact_mean():
    reg = MetricsRegistry()
    h = reg.histogram("t")
    for v in (0.001, 0.002, 0.004, 0.4):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and s["min"] == 0.001 and s["max"] == 0.4
    np.testing.assert_allclose(s["mean"], 0.407 / 4)
    assert s["p50"] <= s["p95"] <= s["p99"]
    empty = reg.histogram("empty")
    assert math.isnan(empty.summary()["p50"])
    assert math.isnan(empty.mean)


def test_histogram_spec_validation():
    with pytest.raises(ValueError):
        HistogramSpec(lo=0.0)
    with pytest.raises(ValueError):
        HistogramSpec(growth=1.0)


# -- registry -----------------------------------------------------------------

def test_registry_get_or_create_and_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("a.b")
    assert reg.counter("a.b") is c
    c.inc()
    c.inc(4)
    reg.gauge("g").set(2.5)
    reg.histogram("h").observe(0.01)
    snap = reg.snapshot()
    assert snap["counters"]["a.b"] == 5
    assert snap["gauges"]["g"] == 2.5
    assert snap["histograms"]["h"]["count"] == 1
    reg.reset()
    assert reg.snapshot()["counters"] == {}


def test_disabled_registry_hands_out_shared_nulls():
    reg = MetricsRegistry(enabled=False)
    assert reg.counter("x") is NULL_COUNTER
    assert reg.gauge("x") is NULL_GAUGE
    assert reg.histogram("x") is NULL_HISTOGRAM
    reg.counter("x").inc(100)
    reg.gauge("x").set(9.0)
    reg.histogram("x").observe(1.0)
    assert NULL_COUNTER.value == 0 and NULL_GAUGE.value == 0.0
    assert NULL_HISTOGRAM.count == 0
    assert reg.counters == {} and reg.histograms == {}   # nothing created


def test_default_registry_swap():
    mine = MetricsRegistry()
    prev = set_default_registry(mine)
    try:
        assert default_registry() is mine
    finally:
        set_default_registry(prev)
    assert default_registry() is prev


# -- tracing spans ------------------------------------------------------------

def test_span_nesting_depth_and_totals():
    with Tracer() as tr:
        with span("outer"):
            with span("inner"):
                pass
            with span("inner"):
                pass
    names = [e["name"] for e in tr.events]
    assert names == ["inner", "inner", "outer"]      # close order
    depths = {e["name"]: e["depth"] for e in tr.events}
    assert depths == {"inner": 1, "outer": 0}
    assert tr.total("inner") == sum(tr.durations("inner"))
    assert len(tr.durations("inner")) == 2
    # containment: outer spans its inners
    outer = tr.events[-1]
    for e in tr.events[:2]:
        assert outer["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-9


def test_span_exception_safety():
    with Tracer() as tr:
        with pytest.raises(RuntimeError):
            with span("outer"):
                with span("inner"):
                    raise RuntimeError("boom")
    assert [e["name"] for e in tr.events] == ["inner", "outer"]
    assert all(e["args"]["error"] == "RuntimeError" for e in tr.events)
    assert tr.depth() == 0                 # stack fully unwound
    assert not tracing_active()            # tracer uninstalled


def test_sync_boundary_invariant():
    """A span closing without a device sync is ALWAYS labelled async."""
    with Tracer() as tr:
        with span("synced") as sp:
            sp.sync(jnp.ones(8) * 2)
        with span("unsynced"):
            jnp.ones(8) * 2                # device work, never synced
        with span("declared-async", sync=False):
            pass
    by = {e["name"]: e["args"]["sync"] for e in tr.events}
    assert by == {"synced": "device", "unsynced": "async",
                  "declared-async": "async"}


def test_span_without_tracer_is_shared_noop():
    """With no tracer a span is the profiler annotation alone: nothing
    is recorded on the Python side and ``sync`` never blocks."""
    assert not tracing_active()
    assert type(span("x")) is _AnnotationSpan
    with span("x") as sp:
        sp.set(ignored=1)
        out = sp.sync(jnp.ones(4))         # passthrough
    np.testing.assert_array_equal(np.asarray(out), np.ones(4))


def test_no_tracing_suspends_and_restores():
    with Tracer() as tr:
        assert tracing_active()
        with no_tracing():
            assert not tracing_active()
            with span("invisible"):
                pass
        assert tracing_active()
        with span("visible"):
            pass
    assert [e["name"] for e in tr.events] == ["visible"]


def test_tracer_chrome_export(tmp_path):
    with Tracer() as tr:
        with span("a", foo=1):
            pass
    path = tr.dump(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    (ev,) = doc["traceEvents"]
    assert ev["ph"] == "X" and ev["name"] == "a"
    assert ev["args"]["foo"] == 1 and ev["args"]["sync"] == "async"
    assert ev["dur"] >= 0 and doc["displayTimeUnit"] == "ms"


# -- kernel stats: byte models vs actual oracle array shapes ------------------

def test_model_pack_codes_matches_array_bytes():
    m, k, bits = 8, K, 2
    codes = jnp.zeros((m, k), jnp.int32)
    words = _ops.pack_codes(codes, bits, impl="ref")
    elements, flops, hbm = model("pack_codes", m=m, k=k, w=words.shape[1])
    assert hbm == 4 * (codes.size + words.size)
    assert elements == m * k


def test_model_coded_project_matches_array_bytes():
    m, d, k = 8, D, K
    x = jnp.zeros((m, d))
    r = jnp.zeros((d, k))
    out_elems = m * k
    elements, flops, hbm = model("coded_project", m=m, d=d, k=k)
    assert hbm == 4 * (x.size + r.size + out_elems)
    assert flops == 2 * m * d * k          # one FMA per (m, d, k)


def test_model_packed_topk_matches_array_bytes():
    q, n, k, bits, top_k = 4, 32, K, 2, 3
    qw = PK.pack_codes(jnp.zeros((q, k), jnp.int32), bits)
    dbw = PK.pack_codes(jnp.zeros((n, k), jnp.int32), bits)
    vals, ids = _ops.packed_topk(qw, dbw, bits, k, top_k, impl="ref")
    elements, flops, hbm = model("packed_topk", q=q, n=n,
                                 w=qw.shape[1], top_k=top_k)
    assert hbm == 4 * (qw.size + dbw.size + vals.size + ids.size)
    # masked variant adds exactly the packed validity bitmask
    _, _, hbm_m = model("packed_topk_masked", q=q, n=n, w=qw.shape[1],
                        top_k=top_k)
    assert hbm_m - hbm == 4 * PK.bitmask_width(n)


def test_kernel_stats_accumulate_and_traced_flag():
    ks = KernelStats()
    prev = set_kernel_stats(ks)
    try:
        codes = jnp.zeros((8, K), jnp.int32)
        _ops.pack_codes(codes, 2, impl="ref")          # eager dispatch
        fn = jax.jit(lambda c: _ops.pack_codes(c, 2, impl="ref"))
        fn(codes)                                      # records at trace
        fn(codes)                                      # cached: no record
        f = ks.snapshot()["pack_codes"]
        assert f["calls"] == 2 and f["traced_calls"] == 1
        assert f["elements"] == 2 * 8 * K
    finally:
        set_kernel_stats(prev)


def test_kernel_stats_disabled_by_registry_switch():
    ks = KernelStats()
    prev_ks = set_kernel_stats(ks)
    prev_reg = set_default_registry(MetricsRegistry(enabled=False))
    try:
        _ops.pack_codes(jnp.zeros((4, K), jnp.int32), 2, impl="ref")
        assert ks.snapshot() == {}
    finally:
        set_default_registry(prev_reg)
        set_kernel_stats(prev_ks)


def test_roofline_table_terms_consistent():
    ks = KernelStats()
    ks.record("coded_project", m=64, d=D, k=K)
    hw = V5E
    row = ks.roofline_table(hw)["coded_project"]
    np.testing.assert_allclose(row["t_compute_s"],
                               row["flops"] / hw.peak_flops)
    np.testing.assert_allclose(row["t_memory_s"],
                               row["hbm_bytes"] / hw.hbm_bw)
    assert row["t_model_s"] == max(row["t_compute_s"], row["t_memory_s"])
    assert row["bound"] in ("compute", "memory")
    np.testing.assert_allclose(row["intensity"],
                               row["flops"] / row["hbm_bytes"])


# -- exporters ----------------------------------------------------------------

def test_snapshot_and_prometheus_export():
    reg = MetricsRegistry()
    reg.counter("serve.queries").inc(3)
    reg.gauge("index.live_rows").set(7)
    h = reg.histogram("serve.flush_s")
    for v in (0.001, 0.002, 0.4):
        h.observe(v)
    ks = KernelStats()
    ks.record("pack_codes", m=4, k=K, w=1)
    snap = snapshot(reg, ks)
    assert snap["counters"]["serve.queries"] == 3
    assert "pack_codes" in snap["kernels"] and "roofline" in snap
    json.dumps(snap)                       # JSON-serializable end to end

    text = to_prometheus(reg)
    assert "serve_queries_total 3" in text
    assert "index_live_rows 7" in text
    assert 'serve_flush_s_bucket{le="+Inf"} 3' in text
    assert "serve_flush_s_count 3" in text
    # cumulative bucket counts are non-decreasing
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("serve_flush_s_bucket")]
    assert cums == sorted(cums) and cums[-1] == 3


# -- instrumented layers ------------------------------------------------------

def test_service_metrics_under_mutation_and_search():
    rng = np.random.default_rng(5)
    eng = MutableAnnEngine(_crp(), band_spec=BAND, tail_rows=64)
    svc = AnnService(eng, AnnServiceConfig(top_k=3, buckets=(1, 4),
                                           cache_size=8))
    svc.add(jnp.asarray(rng.normal(size=(20, D)), jnp.float32))
    q = jnp.asarray(rng.normal(size=(D,)), jnp.float32)
    with Tracer() as tr:
        svc.submit(q)
        svc.flush()
        svc.submit(q)
        svc.flush()                            # cache hit
        assert svc.stats["queries"] == 2
        assert svc.stats["cache_hits"] == 1
        assert svc.stats["cache_misses"] == 1
        assert svc.stats["cache_invalidations"] == 0
        # a mutation invalidates the (non-empty) cache on the next flush
        svc.add(jnp.asarray(rng.normal(size=(4, D)), jnp.float32))
        svc.submit(q)
        svc.flush()
    assert svc.stats["cache_invalidations"] == 1
    assert svc.stats["cache_misses"] == 2
    reg = svc.registry
    assert reg.histograms["serve.flush_s"].count == 3
    assert reg.histograms["serve.ticket_age_s"].count == 3
    # two engine searches: the cache hit never reaches the engine
    assert len(tr.durations("engine.search")) == 2
    assert reg.gauges["serve.pending"].value == 0.0
    # stats is a read-only compat view
    with pytest.raises(TypeError):
        svc.stats["queries"] = 99
    with pytest.raises(AttributeError):
        svc.stats = {}


def test_service_warmup_and_eviction_counters():
    rng = np.random.default_rng(7)
    eng = MutableAnnEngine(_crp(), band_spec=BAND, tail_rows=64)
    svc = AnnService(eng, AnnServiceConfig(top_k=3, buckets=(1, 4),
                                           cache_size=2))
    svc.add(jnp.asarray(rng.normal(size=(20, D)), jnp.float32))
    svc.warmup(D)
    assert svc.stats["warmup_compiles"] == 2          # one per bucket
    for _ in range(6):
        svc.submit(jnp.asarray(rng.normal(size=(D,)), jnp.float32))
    svc.flush()
    assert len(svc._cache) <= 2
    assert svc.stats["cache_evictions"] >= 4


def test_ingest_and_index_metrics_with_compaction():
    rng = np.random.default_rng(9)
    eng = MutableAnnEngine(_crp(), band_spec=BAND, tail_rows=64)
    ids = eng.ingest(jnp.asarray(rng.normal(size=(200, D)), jnp.float32),
                     chunk_rows=64)
    store = eng.store
    reg = store.registry
    assert reg.counters["index.rows_appended"].value == 200
    assert reg.counters["index.seals"].value == 3      # 200 rows / 64
    assert reg.gauges["index.live_rows"].value == 200
    assert reg.gauges["index.live_fraction"].value == 1.0
    eng.delete(ids[:150])
    assert reg.counters["index.rows_deleted"].value == 150
    np.testing.assert_allclose(reg.gauges["index.live_fraction"].value,
                               50 / 200)
    before = store.stats()
    rep = eng.compact()
    assert rep["rows_dropped"] > 0
    assert reg.counters["index.compactions"].value == 1
    assert reg.counters["index.compact_rows_dropped"].value \
        == rep["rows_dropped"]
    assert reg.gauges["index.segments"].value < before["n_segments"]
    np.testing.assert_allclose(reg.gauges["index.live_fraction"].value,
                               store.n_live / store.n_rows)


def test_pipeline_stats_compat_and_registry():
    from repro.encode.pipeline import IngestPipeline
    from repro.index.segment_log import SegmentLogStore
    crp = _crp()
    store = SegmentLogStore(K, 2, tail_rows=64)
    pipe = IngestPipeline(crp.stream_encoder(), store, chunk_rows=32)
    rng = np.random.default_rng(11)
    with Tracer() as tr:
        pipe.ingest(jnp.asarray(rng.normal(size=(70, D)), jnp.float32))
    assert pipe.stats["rows"] == 70 and pipe.stats["chunks"] == 3
    assert pipe.stats["packed_bytes"] == \
        pipe.registry.counters["encode.packed_bytes"].value
    chunks = [e for e in tr.events if e["name"] == "encode.chunk"]
    assert [e["args"]["rows"] for e in chunks] == [32, 32, 6]
    assert all(e["args"]["sync"] == "device" for e in chunks)
    with pytest.raises(TypeError):
        pipe.stats["rows"] = 0             # read-only compat view


def test_traced_search_emits_scored_spans():
    """Default scored search emits the single ``search.fused`` span;
    ``fused=False`` emits the two-stage ``search.coarse``/
    ``search.rerank`` pair — all device-synced, tracing never changing
    results."""
    rng = np.random.default_rng(13)
    eng = MutableAnnEngine(_crp(), band_spec=BAND, tail_rows=64)
    eng.add(jnp.asarray(rng.normal(size=(96, D)), jnp.float32))
    q = jnp.asarray(rng.normal(size=(4, D)), jnp.float32)
    ids_plain, rho_plain = eng.search(q, 3, scored=True, chunk_q=4)
    with Tracer() as tr:
        ids_tr, rho_tr = eng.search(q, 3, scored=True, chunk_q=4)
    assert tr.total("search.fused") > 0
    assert all(e["args"]["sync"] == "device" for e in tr.events
               if e["name"].startswith("search."))
    np.testing.assert_array_equal(np.asarray(ids_tr),
                                  np.asarray(ids_plain))
    np.testing.assert_allclose(np.asarray(rho_tr), np.asarray(rho_plain),
                               rtol=1e-6)
    with Tracer() as tr2:
        ids_two, _ = eng.search(q, 3, scored=True, chunk_q=4, fused=False)
    # the legacy path keeps its per-stage spans and the same results
    assert tr2.total("search.coarse") > 0
    assert tr2.total("search.rerank") > 0
    assert tr2.total("search.fused") == 0
    np.testing.assert_array_equal(np.asarray(ids_two),
                                  np.asarray(ids_plain))


def test_obs_cycle_trace_artifact_min_events_and_nesting():
    """The committed TRACE_obs_cycle.json (regenerated by
    benchmarks/obs_bench.py) covers the full service cycle — ingest,
    search, classify, learn, compact — and its spans nest properly:
    same-track spans are either disjoint or fully contained (the
    timestamp-containment encoding Perfetto builds flames from)."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "TRACE_obs_cycle.json")
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    assert len(evs) >= 18                  # full cycle, not a stub
    names = {e["name"] for e in evs}
    assert {"encode.ingest", "encode.chunk", "serve.flush",
            "serve.classify", "learn.fit", "index.compact"} <= names
    assert any(n.startswith("search.") for n in names)
    for e in evs:
        assert e["ph"] == "X" and e["dur"] >= 0
        assert e["args"]["sync"] in ("device", "async")
    # pairwise nesting per track: overlap implies containment
    by_tid = {}
    for e in evs:
        by_tid.setdefault(e["tid"], []).append(e)
    for track in by_tid.values():
        for i, a in enumerate(track):
            for b in track[i + 1:]:
                a0, a1 = a["ts"], a["ts"] + a["dur"]
                b0, b1 = b["ts"], b["ts"] + b["dur"]
                eps = 1.0                  # us rounding slop
                overlap = a0 < b1 and b0 < a1
                contained = (a0 >= b0 - eps and a1 <= b1 + eps) or \
                            (b0 >= a0 - eps and b1 <= a1 + eps)
                assert not overlap or contained, (a["name"], b["name"])
    # ingest chunks nest inside their ingest span
    ing = next(e for e in evs if e["name"] == "encode.ingest")
    for e in evs:
        if e["name"] == "encode.chunk":
            assert ing["ts"] - 1.0 <= e["ts"]
            assert e["ts"] + e["dur"] <= ing["ts"] + ing["dur"] + 1.0


def test_immutable_engine_traced_scored_split_matches_untraced():
    from repro.ann import AnnEngine
    rng = np.random.default_rng(17)
    corpus = jnp.asarray(rng.normal(size=(128, D)), jnp.float32)
    eng = AnnEngine.build(_crp(), corpus, BAND)
    q = corpus[:4] + 0.01
    ids_plain, rho_plain = eng.search(q, 3, scored=True, chunk_q=4)
    with Tracer() as tr:
        ids_tr, rho_tr = eng.search(q, 3, scored=True, chunk_q=4)
    assert tr.total("search.fused") > 0
    with Tracer() as tr2:
        ids_two, rho_two = eng.search(q, 3, scored=True, chunk_q=4,
                                      fused=False)
    assert tr2.total("search.coarse") > 0 and tr2.total("search.rerank") > 0
    np.testing.assert_array_equal(np.asarray(ids_tr),
                                  np.asarray(ids_plain))
    np.testing.assert_array_equal(np.asarray(ids_two),
                                  np.asarray(ids_plain))
    np.testing.assert_allclose(np.asarray(rho_tr), np.asarray(rho_plain),
                               rtol=1e-6)
