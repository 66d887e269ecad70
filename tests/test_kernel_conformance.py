"""Cross-kernel conformance suite: every Pallas kernel family vs its
``kernels/ref.py`` oracle over one shared differential grid.

This file replaces the ad-hoc per-subsystem bit-exactness tests that
used to live in test_kernels/test_ann/test_rank/test_learn/test_encode:
one grid (all schemes x 1/2/4-bit packing x odd / non-power-of-2 shapes
x random tombstone densities x f32/bf16/int8 tables), one assertion
style (bit-exact, values AND tie-broken ids), every family held to it.
Kernels run in interpret mode with deliberately small block sizes so
row/word/query padding and multi-tile carry paths are always exercised.

The fused single-pass scored kernel gets the deepest treatment: it is
checked against its own oracle (``fused_scored_topk_ref``), against the
two-stage pipeline it replaces (``two_stage_scored_ref`` — the
coarse-top-m + LUT-re-rank semantics are the contract), and against a
block-size-invariance property (results must not depend on the tile
shape) driven through ``_hypothesis_compat``.

The quick subgrid runs by default; the full grid rides behind the
``slow`` marker (still part of tier-1 — the marker only lets a fast
iteration loop deselect it with ``-m "not slow"``).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, strategies as st

from repro.core import packing as PK
from repro.core.schemes import CodeSpec, sample_offsets
from repro.kernels import ops, ref
from repro.kernels.collision import collision_counts_pallas
from repro.kernels.encode_fused import code_pack_pallas, encode_fused_pallas
from repro.kernels.fused_scored import (fused_scored_topk_masked_pallas,
                                        fused_scored_topk_pallas)
from repro.kernels.pack_codes import pack_codes_pallas
from repro.kernels.packed_collision import (packed_collision_counts_pallas,
                                            packed_topk_masked_pallas,
                                            packed_topk_pallas)
from repro.kernels.packed_linear import (packed_linear_bwd_masked_pallas,
                                         packed_linear_bwd_pallas,
                                         packed_linear_fwd_masked_pallas,
                                         packed_linear_fwd_pallas)
from repro.kernels.packed_lut import (packed_lut_rerank_pallas,
                                      packed_lut_topk_masked_pallas,
                                      packed_lut_topk_pallas)

slow = pytest.mark.slow

# -- the shared grid ----------------------------------------------------------
# scheme, bin width -> packed field width 1/2/4 bits (CodeSpec.bits)
SCHEMES = [
    pytest.param("sign", 1.0, id="sign-1b"),
    pytest.param("2bit", 0.75, id="2bit-2b"),
    pytest.param("uniform", 1.0, marks=slow, id="uniform-4b"),
    pytest.param("offset", 1.5, marks=slow, id="offset-4b"),
]
# (q, n, k): odd / non-power-of-2 everywhere, k never divides 32/bits
SHAPES = [
    pytest.param(3, 37, 17, id="3x37x17"),
    pytest.param(5, 130, 33, id="5x130x33"),
    pytest.param(8, 130, 64, marks=slow, id="8x130x64"),
    pytest.param(2, 33, 96, marks=slow, id="2x33x96"),
]
DENSITIES = [
    pytest.param(0.0, id="all-dead"),
    pytest.param(0.35, id="sparse"),
    pytest.param(1.0, id="all-live"),
]
TABLE_DTYPES = [
    pytest.param("f32", id="f32"),
    pytest.param("bf16", id="bf16"),
    pytest.param("int8", id="int8"),
]
BITS = [1, 2, pytest.param(4, marks=slow)]


def _codes(key, shape, bits):
    return jax.random.randint(key, shape, 0, 1 << bits)


def _tables(key, q, k, bits, table_dtype):
    """Random per-query LUTs in the flat [Q, F*P] layout the kernels
    take; int8 comes with power-of-two scales (the dtype's contract)."""
    fp = PK.packed_width(k, bits) * PK.codes_per_word(bits) * (1 << bits)
    n_words = PK.packed_width(k, bits)
    t = jax.random.normal(key, (q, fp), jnp.float32)
    if table_dtype == "bf16":
        return t.astype(jnp.bfloat16), None
    if table_dtype == "int8":
        ti = jax.random.randint(key, (q, fp), -127, 128).astype(jnp.int8)
        scales = jnp.exp2(jax.random.randint(
            jax.random.fold_in(key, 1), (q, n_words), -8, 2)
            .astype(jnp.float32))
        return ti, scales
    return t, None


def _mask(key, n, density):
    flags = jax.random.bernoulli(key, density, (n,))
    return flags, PK.pack_bitmask(flags)


def _eq(got, want, label=""):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want), label)


def _eq_pairs(got, want, label=""):
    for g, w in zip(got, want):
        _eq(g, w, label)


# -- encode path: project -> code -> pack -------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32,
                                   pytest.param(jnp.bfloat16, marks=slow)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("scheme,w", SCHEMES)
@pytest.mark.parametrize("q,n,k", SHAPES)
def test_coded_project_conformance(scheme, w, q, n, k, dtype):
    m, d = n, max(q * 8, 24)            # reuse grid dims as [m, d, k]
    key = jax.random.PRNGKey(m * 7 + k)
    x = jax.random.normal(key, (m, d), dtype)
    r = jax.random.normal(jax.random.fold_in(key, 1), (d, k), dtype)
    off = sample_offsets(jax.random.fold_in(key, 2), k, w)
    spec = CodeSpec(scheme, w)
    got = ops.coded_project(x, r, spec, off, impl="pallas", block_m=32,
                            block_k=32, block_d=64)
    want = ref.coded_project_ref(x, r, spec, off)
    # floor() at bin boundaries can flip one ulp between accumulation
    # orders for bf16 inputs; allow a vanishing fraction there
    tol = 0 if dtype == jnp.float32 else max(2, int(0.001 * got.size))
    mism = int(jnp.sum(got != want))
    assert mism <= tol, f"{mism}/{got.size} mismatches"


@pytest.mark.parametrize("dtype", [jnp.float32,
                                   pytest.param(jnp.bfloat16, marks=slow)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("scheme,w", SCHEMES)
@pytest.mark.parametrize("q,n,k", SHAPES)
def test_encode_fused_conformance(scheme, w, q, n, k, dtype):
    m, d = n, max(q * 8, 24)
    key = jax.random.PRNGKey(m * 13 + k)
    x = jax.random.normal(key, (m, d), dtype)
    r = jax.random.normal(jax.random.fold_in(key, 1), (d, k), dtype)
    off = sample_offsets(jax.random.fold_in(key, 2), k, w)
    spec = CodeSpec(scheme, w)
    got = encode_fused_pallas(x, r, spec, off, interpret=True,
                              block_m=32, block_d=64)
    want = ref.encode_fused_ref(x, r, spec, off)
    assert got.shape == want.shape == (m, PK.packed_width(k, spec.bits))
    if dtype == jnp.float32:
        _eq(got, want)
    else:
        cg = PK.unpack_codes(got, spec.bits, k)
        cw = PK.unpack_codes(want, spec.bits, k)
        mism = int(jnp.sum(cg != cw))
        assert mism <= max(2, int(0.001 * m * k)), mism


@pytest.mark.parametrize("scheme,w", SCHEMES)
@pytest.mark.parametrize("q,n,k", SHAPES)
def test_code_pack_conformance(scheme, w, q, n, k):
    m = n
    key = jax.random.PRNGKey(m + k)
    z = jax.random.normal(key, (m, k)) * 2.0
    off = sample_offsets(jax.random.fold_in(key, 1), k, w)
    spec = CodeSpec(scheme, w)
    _eq(code_pack_pallas(z, spec, off, interpret=True, block_m=32),
        ref.code_pack_ref(z, spec, off))


@pytest.mark.parametrize("bits", BITS + [pytest.param(8, marks=slow)])
@pytest.mark.parametrize("q,n,k", SHAPES)
def test_pack_codes_conformance(bits, q, n, k):
    m = n
    codes = _codes(jax.random.PRNGKey(bits * 31 + m), (m, k), bits)
    _eq(pack_codes_pallas(codes, bits, interpret=True, block_m=32),
        ref.pack_codes_ref(codes, bits))


# -- collision counting -------------------------------------------------------

@pytest.mark.parametrize("q,n,k", SHAPES)
def test_collision_counts_conformance(q, n, k):
    key = jax.random.PRNGKey(q * n)
    cq = _codes(key, (q, k), 2)
    cdb = _codes(jax.random.fold_in(key, 1), (n, k), 2)
    _eq(collision_counts_pallas(cq, cdb, interpret=True, block_q=32,
                                block_n=32, block_k=64),
        ref.collision_counts_ref(cq, cdb))


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("q,n,k", SHAPES)
def test_packed_collision_conformance(bits, q, n, k):
    """Packed XOR/popcount counts == unpacked oracle == packed ref,
    incl. K-padding (k never divides 32/bits on this grid)."""
    key = jax.random.PRNGKey(bits * 100 + q)
    cq, cdb = _codes(key, (q, k), bits), _codes(
        jax.random.fold_in(key, 1), (n, k), bits)
    wq, wdb = PK.pack_codes(cq, bits), PK.pack_codes(cdb, bits)
    want = ref.collision_counts_ref(cq, cdb)
    _eq(ref.packed_collision_ref(wq, wdb, bits, k), want, "ref")
    _eq(packed_collision_counts_pallas(wq, wdb, bits, k, block_q=8,
                                       block_n=16, block_w=2,
                                       interpret=True), want, "pallas")


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("q,n,k", SHAPES)
@pytest.mark.parametrize("top_k", [1, pytest.param(5, marks=slow), 50])
def test_packed_topk_conformance(bits, q, n, k, top_k):
    """Streaming top-k == full-matrix stable top-k, values AND
    tie-broken ids; top_k=50 > n=37 exercises (-1, -1) overflow."""
    key = jax.random.PRNGKey(k + top_k)
    wq = PK.pack_codes(_codes(key, (q, k), bits), bits)
    wdb = PK.pack_codes(_codes(jax.random.fold_in(key, 1), (n, k), bits),
                        bits)
    _eq_pairs(packed_topk_pallas(wq, wdb, bits, k, top_k, block_q=8,
                                 block_n=32, interpret=True),
              ref.packed_topk_ref(wq, wdb, bits, k, top_k))


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("q,n,k", SHAPES)
@pytest.mark.parametrize("density", DENSITIES)
def test_packed_topk_masked_conformance(bits, q, n, k, density):
    key = jax.random.PRNGKey(bits + int(density * 7))
    wq = PK.pack_codes(_codes(key, (q, k), bits), bits)
    wdb = PK.pack_codes(_codes(jax.random.fold_in(key, 1), (n, k), bits),
                        bits)
    flags, vwords = _mask(jax.random.fold_in(key, 9), n, density)
    got = packed_topk_masked_pallas(wq, wdb, vwords, bits, k, 8,
                                    block_q=8, block_n=32, interpret=True)
    _eq_pairs(got, ref.packed_topk_masked_ref(wq, wdb, vwords, bits, k, 8))
    dead = set(np.flatnonzero(~np.asarray(flags)))
    assert not (set(np.asarray(got[1]).ravel()) - {-1}) & dead


# the running top-k merge (shared by every streaming family): ties go to
# the lowest corpus id, running sentinels outlast equal-keyed rows
MERGE_CASES = [
    pytest.param("all-equal", id="all-counts-equal"),
    pytest.param("few-live", id="top_k-gt-live-rows"),
    pytest.param("dead-tile", id="all-tombstoned-tile"),
]


@pytest.mark.parametrize("family", ["counts", "lut", "fused"])
@pytest.mark.parametrize("case", MERGE_CASES)
def test_running_merge_ties_and_sentinels(family, case):
    """Merge tie rule and sentinel padding, bit-exact vs the oracles:
    every row equal to the query (ids must come out 0, 1, 2, ...), fewer
    live rows than top_k ((-1, -1) / (-inf, -1) fill the tail), and a
    whole 32-row tile tombstoned (its rows never surface)."""
    q, n, k, bits, top_k, m = 3, 96, 33, 2, 12, 40
    key = jax.random.PRNGKey(len(case) * 7 + len(family))
    wq = PK.pack_codes(_codes(key, (q, k), bits), bits)
    if case == "all-equal":
        wdb = jnp.broadcast_to(wq[:1], (n, wq.shape[1]))
        live = jnp.ones((n,), bool)
    else:
        wdb = PK.pack_codes(_codes(jax.random.fold_in(key, 1), (n, k),
                                   bits), bits)
        rows = jnp.arange(n)
        live = (rows % 19 == 3) if case == "few-live" else \
            (rows // 32 != 1)
    vwords = PK.pack_bitmask(live)
    tab, _ = _tables(jax.random.fold_in(key, 2), q, k, bits, "f32")
    blocks = dict(block_q=8, block_n=32, interpret=True)
    if family == "counts":
        got = packed_topk_masked_pallas(wq, wdb, vwords, bits, k, top_k,
                                        **blocks)
        want = ref.packed_topk_masked_ref(wq, wdb, vwords, bits, k, top_k)
    elif family == "lut":
        got = packed_lut_topk_masked_pallas(tab, wdb, vwords, bits, top_k,
                                            **blocks)
        want = ref.packed_lut_topk_masked_ref(tab, wdb, vwords, bits, top_k)
    else:
        got = fused_scored_topk_masked_pallas(wq, tab, wdb, vwords, bits,
                                              k, m, top_k, **blocks)
        want = ref.fused_scored_topk_masked_ref(wq, tab, wdb, vwords, bits,
                                                k, m, top_k)
    _eq_pairs(got, want)
    ids = np.asarray(got[1])
    if case == "all-equal":
        assert (ids == np.arange(top_k)[None, :]).all()
    elif case == "few-live":
        n_live = int(np.sum(np.asarray(live)))
        assert n_live < top_k and (ids[:, n_live:] == -1).all()
        empty = np.asarray(got[0])[:, n_live:]
        assert (empty == -1).all() if family == "counts" else \
            np.isneginf(empty).all()
    else:
        assert not ((ids >= 32) & (ids < 64)).any()


# -- LUT scoring --------------------------------------------------------------

@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("q,n,k", SHAPES)
@pytest.mark.parametrize("table_dtype", TABLE_DTYPES[:2])
def test_lut_topk_conformance(bits, q, n, k, table_dtype):
    key = jax.random.PRNGKey(q * k + bits)
    tab, _ = _tables(key, q, k, bits, table_dtype)
    wdb = PK.pack_codes(_codes(jax.random.fold_in(key, 1), (n, k), bits),
                        bits)
    _eq_pairs(packed_lut_topk_pallas(tab, wdb, bits, 7, interpret=True,
                                     block_q=8, block_n=32),
              ref.packed_lut_topk_ref(tab, wdb, bits, 7))


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("q,n,k", SHAPES[:2] + SHAPES[2:3])
@pytest.mark.parametrize("density", DENSITIES)
def test_lut_topk_masked_conformance(bits, q, n, k, density):
    key = jax.random.PRNGKey(bits * 5 + int(density * 7))
    tab, _ = _tables(key, q, k, bits, "f32")
    wdb = PK.pack_codes(_codes(jax.random.fold_in(key, 1), (n, k), bits),
                        bits)
    flags, vwords = _mask(jax.random.fold_in(key, 9), n, density)
    got = packed_lut_topk_masked_pallas(tab, wdb, vwords, bits, 7,
                                        interpret=True, block_q=8,
                                        block_n=32)
    _eq_pairs(got, ref.packed_lut_topk_masked_ref(tab, wdb, vwords, bits, 7))
    dead = set(np.flatnonzero(~np.asarray(flags)))
    assert not (set(np.asarray(got[1]).ravel()) - {-1}) & dead


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("table_dtype", TABLE_DTYPES[:2])
def test_lut_rerank_conformance(bits, table_dtype):
    """Candidate re-rank with random invalid (-1) slots."""
    q, n, m, k = 13, 130, 50, 33
    key = jax.random.PRNGKey(3 + bits)
    tab, _ = _tables(key, q, k, bits, table_dtype)
    wdb = PK.pack_codes(_codes(jax.random.fold_in(key, 1), (n, k), bits),
                        bits)
    cand_ids = jax.random.randint(jax.random.fold_in(key, 5), (q, m), -1, n)
    cand = jnp.take(wdb, jnp.clip(cand_ids, 0, n - 1), axis=0)
    valid = cand_ids >= 0
    _eq_pairs(packed_lut_rerank_pallas(tab, cand, valid, bits, 7,
                                       interpret=True, block_q=8,
                                       block_m=64),
              ref.packed_lut_rerank_ref(tab, cand, valid, bits, 7))


# -- packed-linear classifier kernels ----------------------------------------

@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("density", [None] + DENSITIES)
def test_packed_linear_conformance(bits, direction, density):
    n_cls, n, k = 3, 130, 33
    key = jax.random.PRNGKey(bits * 11 + (0 if density is None
                                          else int(density * 7)))
    tab, _ = _tables(key, n_cls, k, bits, "f32")
    words = PK.pack_codes(
        _codes(jax.random.fold_in(key, 1), (n, k), bits), bits)
    g = jax.random.normal(jax.random.fold_in(key, 2), (n_cls, n))
    if density is None:
        if direction == "fwd":
            _eq(packed_linear_fwd_pallas(tab, words, bits, interpret=True,
                                         block_c=2, block_n=32),
                ref.packed_linear_fwd_ref(tab, words, bits))
        else:
            _eq(packed_linear_bwd_pallas(g, words, bits, interpret=True,
                                         block_c=2, block_n=32),
                ref.packed_linear_bwd_ref(g, words, bits, block_c=2,
                                          block_n=32))
        return
    flags, vw = _mask(jax.random.fold_in(key, 9), n, density)
    if direction == "fwd":
        got = packed_linear_fwd_masked_pallas(tab, words, vw, bits,
                                              interpret=True, block_c=2,
                                              block_n=32)
        _eq(got, ref.packed_linear_fwd_masked_ref(tab, words, vw, bits))
        assert (np.asarray(got)[:, ~np.asarray(flags)] == 0.0).all()
    else:
        got = packed_linear_bwd_masked_pallas(g, words, vw, bits,
                                              interpret=True, block_c=2,
                                              block_n=32)
        _eq(got, ref.packed_linear_bwd_masked_ref(g, words, vw, bits,
                                                  block_c=2, block_n=32))
        # masking == zeroing dead rows' gradients by hand
        g0 = jnp.where(jnp.asarray(flags)[None, :], g, 0.0)
        _eq(got, ref.packed_linear_bwd_ref(g0, words, bits, block_c=2,
                                           block_n=32))


# -- fused single-pass scored search ------------------------------------------

def _fused_problem(key, q, n, k, bits, table_dtype):
    wq = PK.pack_codes(_codes(key, (q, k), bits), bits)
    wdb = PK.pack_codes(_codes(jax.random.fold_in(key, 1), (n, k), bits),
                        bits)
    tab, scales = _tables(jax.random.fold_in(key, 2), q, k, bits,
                          table_dtype)
    return wq, wdb, tab, scales


@pytest.mark.parametrize("table_dtype", TABLE_DTYPES)
@pytest.mark.parametrize("q,n,k", SHAPES)
@pytest.mark.parametrize("bits", BITS)
def test_fused_scored_conformance(bits, q, n, k, table_dtype):
    """The single-pass kernel is bit-exact vs its oracle AND vs the
    two-stage coarse+re-rank pipeline it replaces (f32/bf16; the int8
    path has no two-stage counterpart — oracle only)."""
    m, top_k = max(5, n // 4), 7
    key = jax.random.PRNGKey(bits * 301 + q * n + k)
    wq, wdb, tab, scales = _fused_problem(key, q, n, k, bits, table_dtype)
    got = fused_scored_topk_pallas(wq, tab, wdb, bits, k, m, top_k,
                                   scales=scales, block_q=8, block_n=32,
                                   interpret=True)
    want = ref.fused_scored_topk_ref(wq, tab, wdb, bits, k, m, top_k,
                                     scales=scales)
    _eq_pairs(got, want, "kernel vs fused ref")
    if scales is None:
        _eq_pairs(want,
                  ref.two_stage_scored_ref(wq, tab, wdb, bits, k, m, top_k),
                  "fused ref vs two-stage ref")


@pytest.mark.parametrize("table_dtype", TABLE_DTYPES)
@pytest.mark.parametrize("density", DENSITIES)
@pytest.mark.parametrize("bits", BITS)
def test_fused_scored_masked_conformance(bits, density, table_dtype):
    """Masked variant under random tombstone bitmasks: kernel == oracle
    == masked two-stage; dead rows never surface."""
    q, n, k, m, top_k = 5, 130, 33, 20, 7
    key = jax.random.PRNGKey(bits * 17 + int(density * 7))
    wq, wdb, tab, scales = _fused_problem(key, q, n, k, bits, table_dtype)
    flags, vwords = _mask(jax.random.fold_in(key, 9), n, density)
    got = fused_scored_topk_masked_pallas(wq, tab, wdb, vwords, bits, k,
                                          m, top_k, scales=scales,
                                          block_q=8, block_n=32,
                                          interpret=True)
    want = ref.fused_scored_topk_masked_ref(wq, tab, wdb, vwords, bits, k,
                                            m, top_k, scales=scales)
    _eq_pairs(got, want, "kernel vs fused ref")
    if scales is None:
        _eq_pairs(want, ref.two_stage_scored_masked_ref(
            wq, tab, wdb, vwords, bits, k, m, top_k), "vs two-stage")
    dead = set(np.flatnonzero(~np.asarray(flags)))
    assert not (set(np.asarray(got[1]).ravel()) - {-1}) & dead


@pytest.mark.parametrize("case", [
    pytest.param(dict(n=9, m=50, top_k=4), id="rerank_m-gt-corpus"),
    pytest.param(dict(n=30, m=8, top_k=20), id="top_k-gt-candidates"),
    pytest.param(dict(n=1, m=1, top_k=1), id="single-row"),
    pytest.param(dict(n=40, m=40, top_k=40), id="everything-survives"),
])
def test_fused_scored_edge_cases(case):
    """Degenerate geometries: overflow slots are (-inf, -1) and the
    fused and two-stage rankings still agree slot for slot."""
    q, k, bits = 4, 33, 2
    n, m, top_k = case["n"], case["m"], case["top_k"]
    key = jax.random.PRNGKey(n * m + top_k)
    wq, wdb, tab, _ = _fused_problem(key, q, n, k, bits, "f32")
    got = fused_scored_topk_pallas(wq, tab, wdb, bits, k, m, top_k,
                                   block_q=8, block_n=32, interpret=True)
    want = ref.fused_scored_topk_ref(wq, tab, wdb, bits, k, m, top_k)
    _eq_pairs(got, want)
    _eq_pairs(want, ref.two_stage_scored_ref(wq, tab, wdb, bits, k, m,
                                             top_k))
    pad = min(n, m)
    assert (np.asarray(got[1])[:, pad:] == -1).all()
    assert np.isneginf(np.asarray(got[0])[:, pad:]).all()


def test_fused_scored_all_rows_tombstoned():
    """A fully-dead segment returns pure sentinels from both paths."""
    q, n, k, bits = 3, 64, 33, 2
    key = jax.random.PRNGKey(0)
    wq, wdb, tab, _ = _fused_problem(key, q, n, k, bits, "f32")
    vwords = PK.pack_bitmask(jnp.zeros((n,), bool))
    got = fused_scored_topk_masked_pallas(wq, tab, wdb, vwords, bits, k,
                                          16, 5, block_q=8, block_n=32,
                                          interpret=True)
    assert (np.asarray(got[1]) == -1).all()
    assert np.isneginf(np.asarray(got[0])).all()
    _eq_pairs(got, ref.two_stage_scored_masked_ref(wq, tab, wdb, vwords,
                                                   bits, k, 16, 5))


@settings(max_examples=8, deadline=None)
@given(st.sampled_from([8, 16, 32]),        # block_q
       st.sampled_from([32, 64, 128]),      # block_n
       st.integers(min_value=1, max_value=90),        # n
       st.integers(min_value=1, max_value=40),        # m
       st.integers(min_value=0, max_value=2**31 - 1))  # seed
def test_fused_scored_block_size_invariance(block_q, block_n, n, m, seed):
    """Property: the fused result is a pure function of the inputs —
    tile shape never changes values or ids (the autotuner's license to
    sweep block sizes)."""
    q, k, bits, top_k = 3, 17, 2, 5
    key = jax.random.PRNGKey(seed)
    wq, wdb, tab, _ = _fused_problem(key, q, n, k, bits, "f32")
    got = fused_scored_topk_pallas(wq, tab, wdb, bits, k, m, top_k,
                                   block_q=block_q, block_n=block_n,
                                   interpret=True)
    _eq_pairs(got, ref.fused_scored_topk_ref(wq, tab, wdb, bits, k, m,
                                             top_k))


def test_ops_dispatch_fused_agrees():
    """ops.fused_scored_topk: ref and pallas impls agree through the
    dispatch chokepoint (and through any autotune-supplied blocks)."""
    q, n, k, bits, m, top_k = 5, 70, 33, 2, 16, 6
    key = jax.random.PRNGKey(11)
    wq, wdb, tab, _ = _fused_problem(key, q, n, k, bits, "f32")
    a = ops.fused_scored_topk(wq, tab, wdb, bits, k, m, top_k, impl="ref")
    b = ops.fused_scored_topk(wq, tab, wdb, bits, k, m, top_k,
                              impl="pallas", block_q=8, block_n=32)
    _eq_pairs(a, b)


def test_ops_dispatch_cpu_uses_ref():
    """impl='auto' resolves to the jnp oracle off-TPU (moved here from
    test_kernels.py — it is a conformance property of the dispatcher)."""
    x = jnp.ones((4, 8), jnp.float32)
    r = jnp.ones((8, 4), jnp.float32)
    out = ops.coded_project(x, r, CodeSpec("sign", 1.0))
    np.testing.assert_array_equal(np.asarray(out), 1)


@pytest.mark.parametrize("backend,auto,interpret", [
    ("tpu", "pallas", False), ("cpu", "ref", True)])
def test_ops_dispatch_policy(monkeypatch, backend, auto, interpret):
    """On a TPU, impl='auto' is the compiled kernel — never the oracle,
    never interpret mode; 'ref' and 'pallas' stay explicit choices."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    assert ops._resolve("auto") == auto
    assert ops._resolve("ref") == "ref"
    assert ops._resolve("pallas") == "pallas"
    assert ops._interpret() is interpret
