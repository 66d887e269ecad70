"""Autotune cache behavior: persistence round-trips, cold-cache
fallbacks, stale-entry filtering — and the one invariant everything
hangs on: a cache entry (fresh, stale, or fabricated) can change
*timing only*, never output bits.
"""
import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import packing as PK
from repro.kernels import autotune, ops, ref


@pytest.fixture
def iso_cache():
    """A fresh process-global cache for the test, restored after."""
    cache = autotune.AutotuneCache()
    prev = autotune.set_cache(cache)
    yield cache
    autotune.set_cache(prev)


def _fused_inputs(q=5, n=70, k=33, bits=2, seed=7):
    key = jax.random.PRNGKey(seed)
    wq = PK.pack_codes(
        jax.random.randint(key, (q, k), 0, 1 << bits), bits)
    wdb = PK.pack_codes(
        jax.random.randint(jax.random.fold_in(key, 1), (n, k), 0,
                           1 << bits), bits)
    fp = wq.shape[1] * (32 // bits) * (1 << bits)
    tab = jax.random.normal(jax.random.fold_in(key, 2), (q, fp))
    return wq, wdb, tab


# -- bucket + cache mechanics -------------------------------------------------

def test_shape_bucket_rounds_to_pow2():
    assert autotune.shape_bucket(n=100000, q=256) == "n131072-q256"
    assert autotune.shape_bucket(n=1, q=0) == "n1-q0"
    # close shapes share a bucket, far shapes never do
    assert (autotune.shape_bucket(n=70000, q=200)
            == autotune.shape_bucket(n=100000, q=256))
    assert (autotune.shape_bucket(n=70000, q=200)
            != autotune.shape_bucket(n=200000, q=200))


def test_cache_roundtrip_via_json(tmp_path, iso_cache):
    path = str(tmp_path / "tune.json")
    cfg = {"block_q": 64, "block_n": 512}
    iso_cache.put("tpu", "fused_scored_topk", "n1024-q8", "float32", cfg)
    iso_cache.save(path)
    reloaded = autotune.AutotuneCache(path)
    assert reloaded.get("tpu", "fused_scored_topk", "n1024-q8",
                        "float32") == cfg
    # the file is plain versioned JSON
    with open(path) as f:
        data = json.load(f)
    assert data["version"] == 1 and len(data["configs"]) == 1


def test_cache_miss_dimensions(iso_cache):
    cfg = {"block_q": 64}
    iso_cache.put("tpu", "packed_topk", "n1024-q8", "uint32", cfg)
    get = iso_cache.get
    assert get("tpu", "packed_topk", "n1024-q8", "uint32") == cfg
    assert get("gpu", "packed_topk", "n1024-q8", "uint32") is None
    assert get("tpu", "packed_topk", "n2048-q8", "uint32") is None
    assert get("tpu", "packed_topk", "n1024-q8", "float32") is None
    assert get("tpu", "packed_topk_masked", "n1024-q8", "uint32") is None


def test_put_rejects_non_sweepable_knobs(iso_cache):
    """Accumulation-order knobs can never enter the cache — that is the
    numerics invariant's write-side gate."""
    with pytest.raises(ValueError, match="non-sweepable"):
        iso_cache.put("tpu", "packed_linear_bwd", "c8-n1024", "float32",
                      {"block_c": 8, "block_n": 512})
    with pytest.raises(ValueError, match="non-sweepable"):
        iso_cache.put("tpu", "encode_fused", "m256", "float32",
                      {"block_d": 64})


def test_stale_entries_filtered_at_read(tmp_path, iso_cache):
    """A cache file written under an older schema (knobs that are no
    longer sweepable) is filtered to the safe subset at read time."""
    path = str(tmp_path / "stale.json")
    key = "tpu|fused_scored_topk|n1024-q8|float32"
    with open(path, "w") as f:
        json.dump({"version": 1, "configs": {
            key: {"block_q": 64, "block_d": 512, "unroll": 4}}}, f)
    cache = autotune.AutotuneCache(path)
    assert cache.get("tpu", "fused_scored_topk", "n1024-q8",
                     "float32") == {"block_q": 64}
    # nothing valid at all -> clean miss, not a crash
    with open(path, "w") as f:
        json.dump({"version": 1, "configs": {key: {"unroll": 4}}}, f)
    assert autotune.AutotuneCache(path).get(
        "tpu", "fused_scored_topk", "n1024-q8", "float32") is None


def test_candidate_configs_full_grid():
    grid = autotune.candidate_configs("fused_scored_topk")
    assert len(grid) == 9 and all(
        set(c) == {"block_q", "block_n"} for c in grid)
    assert len(autotune.candidate_configs("packed_linear_bwd")) == 3


# -- tune() measurement loop --------------------------------------------------

def test_tune_cpu_without_force_is_noop(iso_cache):
    calls = []
    out = autotune.tune("packed_topk", lambda c: calls.append(c),
                        "uint32", dict(q=8, n=64, w=4, top_k=8))
    assert out == {} and calls == [] and len(iso_cache) == 0


def test_tune_injected_measure_picks_argmin(iso_cache):
    """With a deterministic fake measure, tune picks the argmin config,
    records it, and lookup returns exactly it."""
    target = {"block_q": 64, "block_n": 512}

    def fake_measure(run, config):
        run(config)
        return 1.0 if config == target else 2.0 + config["block_q"]

    dims = dict(q=8, n=100, w=4, top_k=8)
    ran = []
    best = autotune.tune("packed_topk", ran.append, "uint32", dims,
                         measure=fake_measure)
    assert best == target
    assert len(ran) == len(autotune.candidate_configs("packed_topk"))
    assert autotune.lookup("packed_topk", "uint32", **dims) == target
    # a different bucket still cold-misses to {}
    assert autotune.lookup("packed_topk", "uint32", q=8, n=100000, w=4,
                           top_k=8) == {}


def test_tune_skips_raising_candidates(iso_cache):
    """Candidates that fail (VMEM overflow etc.) are skipped; the best
    surviving one wins. All failing -> raises, nothing cached."""
    def fragile_measure(run, config):
        if config["block_q"] > 64:
            raise RuntimeError("tile too large")
        return float(config["block_q"])

    dims = dict(q=8, n=100, w=4, top_k=8)
    best = autotune.tune("packed_topk", lambda c: None, "uint32", dims,
                         measure=fragile_measure)
    assert best["block_q"] == 64

    def all_fail(run, config):
        raise RuntimeError("no")

    with pytest.raises(RuntimeError, match="every candidate failed"):
        autotune.tune("packed_topk", lambda c: None, "uint32",
                      dict(q=9, n=5000, w=4, top_k=8), measure=all_fail)
    assert autotune.lookup("packed_topk", "uint32", q=9, n=5000, w=4,
                           top_k=8) == {}


def test_tune_all_failing_names_each_candidate(iso_cache):
    """When no candidate compiles, the error lists every candidate with
    its own failure, so a refusal by the chip's compiler is readable
    from the one exception."""
    def refuse(run, config):
        raise ValueError(f"refused block_n={config['block_n']}")

    with pytest.raises(RuntimeError) as err:
        autotune.tune("packed_topk", lambda c: None, "uint32",
                      dict(q=8, n=64, w=4, top_k=8), measure=refuse)
    msg = str(err.value)
    for config in autotune.candidate_configs("packed_topk"):
        assert str(config) in msg
    assert msg.count("ValueError: refused block_n=") == len(
        autotune.candidate_configs("packed_topk"))
    assert len(iso_cache) == 0


def test_tune_search_ops_with_injected_measure(iso_cache):
    """The service-warmup entry point tunes every search family using
    real (small) arrays, and the recorded winners flow back through
    lookup for the same dims."""
    seen = []

    def measure(run, config):
        run(config)           # must actually execute without raising
        seen.append(config)
        return float(sum(config.values()))

    out = autotune.tune_search_ops(n=128, w=3, bits=2, k=33, q=8,
                                   top_k=5, rerank_m=16,
                                   measure=measure)
    assert set(out) == {"packed_topk", "packed_topk_masked",
                        "fused_scored_topk", "fused_scored_topk_masked",
                        "packed_lut_topk"}
    for op, best in out.items():
        assert best, op       # every family found a winner
    fp = 3 * (32 // 2) * (1 << 2)
    assert autotune.lookup("fused_scored_topk", "float32", q=8, n=128,
                           w=3, t=fp, top_k=5) == out["fused_scored_topk"]


def test_tune_search_ops_cpu_default_noop(iso_cache):
    assert autotune.tune_search_ops(n=64, w=3, bits=2, k=33, q=4) == {}
    assert len(iso_cache) == 0


# -- the invariant: tuned configs change timing, never numerics ---------------

def test_tuned_config_never_changes_results(iso_cache):
    """ops picks up a cached (even adversarially odd) block config for
    the fused op and still returns bit-identical results to the oracle
    and to the untuned call."""
    wq, wdb, tab = _fused_inputs()
    bits, k, m, top_k = 2, 33, 16, 6
    fp = tab.shape[1]
    dims = dict(q=5, n=70, w=wq.shape[1], t=fp, top_k=top_k)

    base = ops.fused_scored_topk(wq, tab, wdb, bits, k, m, top_k,
                                 impl="pallas")
    want = ref.fused_scored_topk_ref(wq, tab, wdb, bits, k, m, top_k)
    for g, w_ in zip(base, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))

    for cfg in ({"block_q": 32, "block_n": 256},
                {"block_q": 128, "block_n": 1024}):
        autotune.record_config("fused_scored_topk", tab.dtype, dims, cfg,
                               cache=iso_cache)
        assert autotune.lookup("fused_scored_topk", tab.dtype,
                               **dims) == cfg
        tuned = ops.fused_scored_topk(wq, tab, wdb, bits, k, m, top_k,
                                      impl="pallas")
        for g, w_ in zip(tuned, base):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))


def test_cold_cache_identical_to_explicit_defaults(iso_cache):
    """Cold cache -> kernel defaults: bit-identical to passing the
    documented default blocks explicitly."""
    wq, wdb, tab = _fused_inputs()
    bits, k, m, top_k = 2, 33, 16, 6
    cold = ops.fused_scored_topk(wq, tab, wdb, bits, k, m, top_k,
                                 impl="pallas")
    explicit = ops.fused_scored_topk(wq, tab, wdb, bits, k, m, top_k,
                                     impl="pallas", block_q=128,
                                     block_n=512)
    for g, w_ in zip(cold, explicit):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))


def test_explicit_blocks_override_cache(iso_cache):
    """Caller-passed block sizes always win over a cached config (the
    dispatch contract _tuned implements)."""
    wq, wdb, tab = _fused_inputs(q=3, n=40)
    dims = dict(q=3, n=40, w=wq.shape[1], t=tab.shape[1], top_k=4)
    autotune.record_config("fused_scored_topk", tab.dtype, dims,
                           {"block_q": 128, "block_n": 1024},
                           cache=iso_cache)
    got = ops.fused_scored_topk(wq, tab, wdb, 2, 33, 8, 4,
                                impl="pallas", block_q=8, block_n=32)
    want = ref.fused_scored_topk_ref(wq, tab, wdb, 2, 33, 8, 4)
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w_))
