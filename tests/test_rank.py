"""repro.rank: non-linear estimators, LUT tables, and the scored
search paths (single-pass fused by default, two-stage as the checked
fallback). Kernel-vs-oracle bit-exactness lives in
test_kernel_conformance.py."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.ann import AnnEngine, BandSpec
from repro.core import packing as PK
from repro.core.estimators import MleRhoEstimator, cell_probs
from repro.core.schemes import CodeSpec
from repro.core.sketch import CodedRandomProjection, SketchConfig
from repro.index import MutableAnnEngine
from repro.kernels import ref
from repro.rank import build_rank_tables
from repro.serve.ann_service import AnnService, AnnServiceConfig

SPECS = [("2bit", 0.75), ("sign", 1.0), ("uniform", 1.0)]


# -- non-linear estimator -----------------------------------------------------

@pytest.mark.parametrize("scheme,w", SPECS)
def test_mle_estimator_monotone_in_rho(scheme, w):
    """The grid-inverted MLE is monotone in the true rho: feeding it the
    *expected* contingency counts of increasing rho must produce a
    non-decreasing (and accurate) rho_hat sequence."""
    spec = CodeSpec(scheme, w)
    est = MleRhoEstimator(spec, grid_size=512)
    rhos = np.linspace(0.0, 0.98, 30)
    n = spec.n_codes
    probs = np.asarray(cell_probs(jnp.asarray(rhos), spec))
    rho_hat = np.asarray(est.from_counts(256.0 * probs.reshape(30, n * n)))
    assert (np.diff(rho_hat) >= 0).all(), rho_hat
    assert np.max(np.abs(rho_hat - rhos)) < 0.01


def test_mle_estimate_from_codes():
    """Sampled correlated projections: the 2-bit MLE recovers rho."""
    rho, k = 0.8, 4096
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (k,))
    y = rho * x + np.sqrt(1 - rho ** 2) * jax.random.normal(
        jax.random.fold_in(key, 1), (k,))
    spec = CodeSpec("2bit", 0.75)
    from repro.core.schemes import encode
    est = MleRhoEstimator(spec)
    got = float(est.estimate(encode(x[None], spec), encode(y[None], spec))[0])
    assert abs(got - rho) < 0.05, got


def test_rank_tables_calibration_roundtrip():
    """rho_from_scores inverts the expected-score curve to ~1e-4."""
    spec = CodeSpec("2bit", 0.75)
    k = 128
    rt = build_rank_tables(spec, k)
    rhos = np.linspace(0.0, 0.95, 16)
    probs = np.asarray(cell_probs(jnp.asarray(rhos), spec))
    n = spec.n_codes
    g = k * np.einsum("gab,ab->g", probs, np.asarray(rt.pair)[:n, :n])
    rho_hat = np.asarray(rt.rho_from_scores(g))
    assert (np.diff(rho_hat) >= 0).all()
    np.testing.assert_allclose(rho_hat, rhos, atol=1e-3)


def test_rank_tables_reject_offset_scheme():
    with pytest.raises(ValueError):
        build_rank_tables(CodeSpec("offset", 1.0), 64)


# -- fused LUT kernels vs oracles ---------------------------------------------

# -- scored search --------------------------------------------------------

def _unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


@pytest.fixture(scope="module")
def scored_world():
    """Clustered corpus + queries with float32 cosine ground truth."""
    d, n_clusters, per, nq = 32, 80, 8, 24
    key = jax.random.PRNGKey(11)
    centers = _unit(jax.random.normal(key, (n_clusters, d)))
    noise = _unit(jax.random.normal(jax.random.fold_in(key, 1),
                                    (n_clusters, per, d)))
    corpus = _unit(0.92 * centers[:, None, :] + np.sqrt(1 - 0.92 ** 2)
                   * noise).reshape(-1, d)
    qn = _unit(jax.random.normal(jax.random.fold_in(key, 2), (nq, d)))
    queries = _unit(0.92 * centers[:nq] + np.sqrt(1 - 0.92 ** 2) * qn)
    crp = CodedRandomProjection(SketchConfig(k=64, scheme="2bit", w=0.75), d)
    engine = AnnEngine.build(crp, corpus, BandSpec(n_tables=8, band_width=4))
    gt = np.asarray(jnp.argsort(-(queries @ corpus.T), axis=1)[:, :10])
    return engine, corpus, queries, gt


def _recall(ids, gt):
    return float(np.mean([len(set(np.asarray(a)) & set(b)) / gt.shape[1]
                          for a, b in zip(ids, gt)]))


def test_two_stage_recall_at_least_collision_only(scored_world):
    """Against float32 cosine ground truth, LUT re-ranked recall@10 must
    be at least collision-count-only recall@10 at equal k."""
    engine, corpus, queries, gt = scored_world
    ids_plain, _ = engine.search(queries, 10, mode="exact")
    ids_scored, rho = engine.search(queries, 10, mode="exact", scored=True,
                                    rerank_m=256)
    r_plain, r_scored = _recall(ids_plain, gt), _recall(ids_scored, gt)
    assert r_scored >= r_plain, (r_scored, r_plain)
    # calibrated rho is descending per row and within [-1, 1]
    rho = np.asarray(rho)
    assert (np.diff(rho, axis=1) <= 1e-6).all()
    assert (rho <= 1.0).all() and (rho >= -1.0).all()


def test_scored_full_coverage_is_global_lut_ranking(scored_world):
    """With rerank_m >= n the coarse stage cannot truncate: two-stage
    results must equal a full-corpus LUT ranking."""
    engine, corpus, queries, gt = scored_world
    n = engine.n
    ids, _ = engine.search(queries, 6, mode="exact", scored=True,
                           rerank_m=n)
    q_codes = engine.encode_queries(queries)
    tab = engine.rank_tables.query_tables(q_codes)
    _, want = ref.packed_lut_topk_ref(tab, engine.store.words,
                                      engine.store.bits, 6)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want))


def test_scored_mutable_matches_immutable(scored_world):
    """Single-segment mutable scored search == immutable scored search
    (same corpus, full coarse coverage)."""
    engine, corpus, queries, gt = scored_world
    crp = engine.sketcher
    m = MutableAnnEngine(crp, band_spec=BandSpec(n_tables=8, band_width=4),
                         tail_rows=1024)
    m.add(corpus)
    ids_m, rho_m = m.search(queries, 5, mode="exact", scored=True,
                            rerank_m=engine.n)
    ids_i, rho_i = engine.search(queries, 5, mode="exact", scored=True,
                                 rerank_m=engine.n)
    np.testing.assert_array_equal(np.asarray(ids_m), np.asarray(ids_i))
    np.testing.assert_allclose(np.asarray(rho_m), np.asarray(rho_i),
                               rtol=1e-6)


def test_scored_mutable_skips_tombstones(scored_world):
    """Deleted rows never appear in scored results."""
    engine, corpus, queries, gt = scored_world
    m = MutableAnnEngine(engine.sketcher,
                         band_spec=BandSpec(n_tables=8, band_width=4),
                         tail_rows=256)  # several segments
    ext = m.add(corpus)
    dead = set(int(i) for i in ext[::3])
    m.delete(sorted(dead))
    ids, _ = m.search(queries, 10, mode="exact", scored=True, rerank_m=64)
    got = set(int(x) for x in np.asarray(ids).ravel()) - {-1}
    assert not got & dead


def test_scored_edge_batches(scored_world):
    """Empty batch and top_k > corpus honor the (-1, -1) fill contract
    in scored mode too."""
    engine, corpus, queries, gt = scored_world
    ids, rho = engine.search(queries[:0], top_k=3, scored=True)
    assert ids.shape == (0, 3) and rho.shape == (0, 3)
    big = engine.n + 4
    ids, rho = engine.search(queries[:2], top_k=big, mode="exact",
                             scored=True)
    assert (np.asarray(ids[:, engine.n:]) == -1).all()
    assert (np.asarray(rho[:, engine.n:]) == -1).all()


def test_scored_lsh_mode(scored_world):
    """LSH + scored: results come from the banded candidate set and
    carry calibrated rho."""
    engine, corpus, queries, gt = scored_world
    ids, rho = engine.search(queries, 5, mode="lsh", n_probes=1,
                             scored=True, rerank_m=128)
    assert (np.asarray(ids[:, 0]) >= 0).all()
    assert _recall(ids, gt[:, :5]) > 0.2


def test_scored_sharded_matches_unsharded(scored_world):
    from jax.sharding import Mesh
    engine, corpus, queries, gt = scored_world
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    ids_s, rho_s = engine.search_sharded(queries, mesh, top_k=4,
                                         scored=True, rerank_m=256)
    ids_e, rho_e = engine.search(queries, top_k=4, mode="exact",
                                 scored=True, rerank_m=256)
    np.testing.assert_array_equal(np.asarray(ids_s), np.asarray(ids_e))
    np.testing.assert_allclose(np.asarray(rho_s), np.asarray(rho_e),
                               rtol=1e-6)


def test_service_scored_mode(scored_world):
    """The serving layer threads scored knobs through and caches on
    them: scored and unscored results never alias one cache entry."""
    engine, corpus, queries, gt = scored_world
    svc_s = AnnService(engine, AnnServiceConfig(top_k=3, scored=True,
                                                rerank_m=64,
                                                buckets=(1, 4)))
    svc_p = AnnService(engine, AnnServiceConfig(top_k=3, buckets=(1, 4)))
    t_s = [svc_s.submit(queries[i]) for i in range(4)]
    t_p = [svc_p.submit(queries[i]) for i in range(4)]
    out_s, out_p = svc_s.flush(), svc_p.flush()
    ids_direct, _ = engine.search(queries[:4], top_k=3, mode="exact",
                                  scored=True, rerank_m=64)
    for i, t in enumerate(t_s):
        np.testing.assert_array_equal(np.asarray(out_s[t][0]),
                                      np.asarray(ids_direct[i]))
    assert svc_s._cache_key(np.zeros(4)) != svc_p._cache_key(np.zeros(4))
    # cache hit on resubmission
    t2 = svc_s.submit(queries[0])
    svc_s.flush()
    assert svc_s.stats["cache_hits"] >= 1
    np.testing.assert_array_equal(np.asarray(svc_s.result(t2)[0]),
                                  np.asarray(ids_direct[0]))


def _metric_reader(name):
    """``read`` of the benchmark's per-layer metric ``name``."""
    import importlib.util
    import pathlib
    path = (pathlib.Path(__file__).resolve().parents[1] / "chipbench"
            / "metrics" / f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_service_counts_lut_chunks(scored_world):
    """A scored flush through the fused kernel counts the 8-row chunks
    it LUT-scored and all of them, and the histogram bins it visited and
    all of them; unscored serving counts none, and the benchmark's
    shares read a percentage of the scored flush."""
    engine, corpus, queries, gt = scored_world
    m = MutableAnnEngine(engine.sketcher, tail_rows=256)
    m.add(corpus)
    scored = AnnService(m, AnnServiceConfig(
        top_k=3, scored=True, rerank_m=4, buckets=(4,), impl="pallas"))
    plain = AnnService(m, AnnServiceConfig(top_k=3, buckets=(4,),
                                           impl="pallas"))
    for svc in (scored, plain):
        for x in queries[:4]:
            svc.submit(x)
        svc.flush()
    s = scored.stats
    assert 0 < s["lut_chunks_scored"] <= s["lut_chunks"]
    assert 0 < s["hist_bins_visited"] <= s["hist_bins"]
    for key in ("lut_chunks_scored", "lut_chunks", "hist_bins_visited",
                "hist_bins"):
        assert plain.stats[key] == 0
    for name in ("kernel.scan_scored_lut_share",
                 "kernel.scan_scored_hist_share"):
        read = _metric_reader(name)
        assert 0 < read({"counters": dict(s)}) <= 100
        assert read({"counters": dict(plain.stats)}) is None
    # a program without the bin counters leaves the share unread
    hist_share = _metric_reader("kernel.scan_scored_hist_share")
    assert hist_share({"counters": {"lut_chunks": 8}}) is None


def test_service_autotune_warmup_both_store_types(scored_world):
    """``autotune_warmup=True`` must survive warmup over both store
    shapes — CodeStore (words array) and SegmentLogStore (packed width
    attr) — and change nothing about the results (on CPU the sweep is
    a no-op by design)."""
    engine, corpus, queries, gt = scored_world
    svc = AnnService(engine, AnnServiceConfig(
        top_k=3, scored=True, rerank_m=64, buckets=(1, 4),
        autotune_warmup=True))
    svc.warmup(corpus.shape[1])
    t = svc.submit(queries[0])
    svc.flush()
    ids_direct, _ = engine.search(queries[:1], top_k=3, mode="exact",
                                  scored=True, rerank_m=64)
    np.testing.assert_array_equal(np.asarray(svc.result(t)[0]),
                                  np.asarray(ids_direct[0]))

    m = MutableAnnEngine(engine.sketcher, tail_rows=128)
    m.add(corpus, np.arange(corpus.shape[0]))
    svc_m = AnnService(m, AnnServiceConfig(
        top_k=3, scored=True, rerank_m=64, buckets=(1, 4),
        autotune_warmup=True))
    svc_m.warmup(corpus.shape[1])
    tm = svc_m.submit(queries[0])
    svc_m.flush()
    np.testing.assert_array_equal(np.asarray(svc_m.result(tm)[0]),
                                  np.asarray(ids_direct[0]))


def test_bf16_tables_end_to_end(scored_world):
    """bf16-quantized tables run the whole scored path and stay close
    to the f32 ranking."""
    engine, corpus, queries, gt = scored_world
    eng_bf16 = AnnEngine(engine.sketcher, engine.store,
                         BandSpec(n_tables=8, band_width=4),
                         db_band_hashes=engine.db_band_hashes,
                         rank_tables=engine.rank_tables.quantize())
    ids_b, _ = eng_bf16.search(queries, 10, mode="exact", scored=True,
                               rerank_m=256)
    ids_f, _ = engine.search(queries, 10, mode="exact", scored=True,
                             rerank_m=256)
    overlap = np.mean([len(set(np.asarray(a)) & set(np.asarray(b))) / 10
                       for a, b in zip(ids_b, ids_f)])
    assert overlap >= 0.8, overlap


# -- single-pass fused scored path (engine level) -----------------------------

def test_fused_matches_two_stage_immutable(scored_world):
    """The default fused path is bit-identical to the two-stage path it
    replaces — ids AND calibrated rho, across rerank_m regimes."""
    engine, corpus, queries, gt = scored_world
    for m in (16, 256, engine.n + 50):      # truncating / ample / m > n
        ids_f, rho_f = engine.search(queries, 10, mode="exact",
                                     scored=True, rerank_m=m, fused=True)
        ids_t, rho_t = engine.search(queries, 10, mode="exact",
                                     scored=True, rerank_m=m, fused=False)
        np.testing.assert_array_equal(np.asarray(ids_f), np.asarray(ids_t))
        np.testing.assert_array_equal(np.asarray(rho_f), np.asarray(rho_t))


def test_fused_matches_two_stage_mutable(scored_world):
    """Fused masked path == two-stage across segments with tombstones;
    segments small enough that rerank_m exceeds some live counts."""
    engine, corpus, queries, gt = scored_world
    m = MutableAnnEngine(engine.sketcher,
                         band_spec=BandSpec(n_tables=8, band_width=4),
                         tail_rows=256)
    ext = m.add(corpus)
    m.delete(sorted(int(i) for i in ext[::3]))
    for rm in (32, 300):
        ids_f, rho_f = m.search(queries, 10, mode="exact", scored=True,
                                rerank_m=rm, fused=True)
        ids_t, rho_t = m.search(queries, 10, mode="exact", scored=True,
                                rerank_m=rm, fused=False)
        np.testing.assert_array_equal(np.asarray(ids_f), np.asarray(ids_t))
        np.testing.assert_array_equal(np.asarray(rho_f), np.asarray(rho_t))


def test_fused_all_rows_tombstoned_segment(scored_world):
    """A segment whose rows are all deleted contributes nothing; with
    everything deleted the engine returns pure sentinels."""
    engine, corpus, queries, gt = scored_world
    m = MutableAnnEngine(engine.sketcher, tail_rows=128)
    ext = m.add(corpus)
    m.delete([int(i) for i in ext if int(i) < 128])  # first segment dead
    ids, _ = m.search(queries, 10, mode="exact", scored=True, rerank_m=64)
    assert not (set(np.asarray(ids).ravel().tolist()) - {-1}) & set(
        range(128))
    m.delete([int(i) for i in ext if int(i) >= 128])
    ids, rho = m.search(queries, 5, mode="exact", scored=True)
    assert (np.asarray(ids) == -1).all()
    assert (np.asarray(rho) == -1.0).all()


def test_fused_sharded_matches_unsharded(scored_world):
    from jax.sharding import Mesh
    engine, corpus, queries, gt = scored_world
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    ids_s, rho_s = engine.search_sharded(queries, mesh, top_k=4,
                                         scored=True, rerank_m=256,
                                         fused=True)
    ids_e, rho_e = engine.search(queries, top_k=4, mode="exact",
                                 scored=True, rerank_m=256, fused=True)
    np.testing.assert_array_equal(np.asarray(ids_s), np.asarray(ids_e))
    np.testing.assert_allclose(np.asarray(rho_s), np.asarray(rho_e),
                               rtol=1e-6)


def test_int8_tables_end_to_end(scored_world):
    """int8 query tables (power-of-two scales) run the fused path end
    to end and stay close to the f32 ranking; the two-stage path
    rejects them loudly."""
    engine, corpus, queries, gt = scored_world
    ids_8, rho_8 = engine.search(queries, 10, mode="exact", scored=True,
                                 rerank_m=256, table_dtype="int8")
    ids_f, _ = engine.search(queries, 10, mode="exact", scored=True,
                             rerank_m=256)
    overlap = np.mean([len(set(np.asarray(a)) & set(np.asarray(b))) / 10
                       for a, b in zip(ids_8, ids_f)])
    assert overlap >= 0.8, overlap
    rho_8 = np.asarray(rho_8)
    assert (rho_8 <= 1.0).all() and (rho_8 >= -1.0).all()
    with pytest.raises(ValueError, match="int8"):
        engine.search(queries, 10, scored=True, table_dtype="int8",
                      fused=False)


def test_int8_quantization_contract(scored_world):
    """query_tables_int8 emits power-of-two scales and reconstructs the
    f32 tables to within one quantization step."""
    engine, corpus, queries, gt = scored_world
    rt = engine.rank_tables
    q_codes = engine.encode_queries(queries[:4])
    qt, scales = rt.query_tables_int8(q_codes)
    s = np.asarray(scales)
    assert (np.exp2(np.round(np.log2(s))) == s).all()   # powers of two
    t32 = np.asarray(rt.query_tables(q_codes, dtype=jnp.float32))
    cpw_p = t32.shape[1] // s.shape[1]
    recon = (np.asarray(qt, np.float32).reshape(4, s.shape[1], cpw_p)
             * s[:, :, None]).reshape(t32.shape)
    assert np.abs(recon - t32).max() <= s.max()
