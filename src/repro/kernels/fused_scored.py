"""Single-pass fused scored search: coarse collision filter + LUT
re-rank in one kernel.

The two-stage scored path (``packed_collision`` top-m -> gather ->
``packed_lut`` re-rank) pays for its statistical win twice: the coarse
stage sorts the full [Q, N] count matrix down to m candidate ids, and
those ids round-trip through HBM to drive a gather before scoring. This
kernel streams the corpus once more instead and never materializes
either: the survivor *rule* of the stable coarse top-m is evaluated
in-VMEM per corpus tile, and survivors' LUT scores enter the running
top-k directly.

Survivor rule. Collision counts live in [-1, k] (-1 = tombstoned or
padded), so the coarse top-m by count is fully described by a threshold
and a tie quota: with A(c) = #{rows : count > c} and t the smallest
c >= 0 with A(c) < m, row n survives iff count > t, or count == t and
its id-ascending rank among the count == t ties is <= m - A(t). That is
exactly the membership of ``ref.topk_stable_ref(counts, m)`` (stable
ties -> lowest id) — but it needs only the (k+1)-bin exceedance
histogram, not a sort.

Two sweeps over the corpus stream (grid minor axis runs 0..2*NT-1; VMEM
scratch persists across the minor axis for a fixed query tile):

sweep A (j < NT)
    XOR/popcount counts per tile, accumulate A(c) for c in 0..k into a
    [k+1, bq] VMEM histogram (tiles are transposed, rows on sublanes and
    queries on lanes, like every streaming kernel here). At the phase boundary (j == NT) the
    histogram inverts into (t, quota) with a min/max reduction — no
    gather, no sort.

sweep B (j >= NT)
    Recompute the tile's counts (cheaper than writing [Q, N] to HBM and
    reading it back), evaluate the survivor rule — id-ascending tie
    ranks come from a sequential per-query tie counter plus an in-tile
    cumsum, computed as a triangular f32 matmul (MXU-friendly; exact
    below 2^24) — LUT-score the tile, mask non-survivors to -inf, and
    merge into the running (scores, ids) top-k exactly like
    ``packed_lut``.

Scoring paths (``packed_lut.lut_scores``): float tables upcast to
float32 before the kernel and
accumulate in (word, field) order (bit-identical to
``ref.lut_scores_rowwise_ref``); int8 tables take per-(query, word)
float32 scales, sum each word's 32/b selected entries exactly in int32,
and join the float32 total as ``score += scale * float(isum)`` in word
order (bit-identical to ``ref.lut_scores_rowwise_int8_ref``). Scales
must be powers of two: the multiply is then exact, so FMA contraction —
which XLA applies or skips depending on the surrounding fusion — cannot
flip a single result bit between kernel and oracle.

Padding: padded query rows get zero words/tables/scales (their outputs
are sliced off); corpus rows past ``n_valid`` (and tombstoned rows in
the masked variant) take count -1, which the survivor rule can never
admit, so they need no separate score mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.packed_collision import (
    _pad, _round_up, _tile_counts, float_key, init_running, key_float,
    live_rows, mask_rows, mask_tail, merge_topk, topk_out_specs,
    transposed_valid)
from repro.kernels.packed_lut import (corpus_words, lut_scores,
                                      NEG_INF_KEY, transposed_tables)

__all__ = ["fused_scored_topk_pallas", "fused_scored_topk_masked_pallas"]

_NEG_INF = float("-inf")


def _row_cumsum(x):
    """Inclusive cumsum down the rows of small non-negative int32
    [bn, L] via a triangular f32 matmul — one MXU op instead of a
    sublane scan; exact while column sums stay below 2^24 (tile heights
    are far below)."""
    n = x.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    tri = (c <= r).astype(jnp.float32)
    return jnp.dot(tri, x.astype(jnp.float32),
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def _fused_scored_kernel(*refs, bits: int, k: int, rerank_m: int,
                         n_valid: int, block_n: int, nt: int,
                         has_mask: bool, has_scales: bool):
    it = iter(refs)
    q_ref, tab_ref, db_ref = next(it), next(it), next(it)
    valid_ref = next(it) if has_mask else None
    scales_ref = next(it) if has_scales else None
    ok_ref, oi_ref = next(it), next(it)
    keys_ref, score_ref, above_ref, thr_ref, quota_ref, ties_ref = (
        next(it), next(it), next(it), next(it), next(it), next(it))
    rk_ref, ri_ref = next(it), next(it)

    j = pl.program_id(1)
    tile = jax.lax.rem(j, nt)

    def tile_counts():
        _tile_counts(q_ref, db_ref, keys_ref, bits=bits, k=k)
        mask_tail(keys_ref, tile * block_n, n_valid, -1)
        if has_mask:
            mask_rows(keys_ref, live_rows(valid_ref), -1)

    @pl.when(j == 0)
    def _init_hist():
        above_ref[...] = jnp.zeros_like(above_ref)

    @pl.when(j < nt)
    def _sweep_a():
        tile_counts()

        def bin_(c, carry):
            above_ref[pl.ds(c, 1), :] += jnp.sum(
                (keys_ref[...] > c).astype(jnp.int32), axis=0,
                keepdims=True)
            return carry

        jax.lax.fori_loop(0, k + 1, bin_, 0)

    @pl.when(j == nt)
    def _invert():
        a = above_ref[...]                                # [k+1, bq]
        below = a < rerank_m          # nonempty: A(k) == 0 < rerank_m
        cidx = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        thr_ref[...] = jnp.min(jnp.where(below, cidx, k + 1), axis=0,
                               keepdims=True)
        # A is non-increasing, so A(t) is the max over satisfied bins
        a_t = jnp.max(jnp.where(below, a, -1), axis=0, keepdims=True)
        quota_ref[...] = rerank_m - a_t
        ties_ref[...] = jnp.zeros_like(ties_ref)
        init_running(rk_ref, ri_ref, NEG_INF_KEY)

    @pl.when(j >= nt)
    def _sweep_b():
        tile_counts()
        counts = keys_ref[...]
        t = thr_ref[...]                                  # [1, bq]
        is_tie = (counts == t).astype(jnp.int32)
        tie_rank = ties_ref[...] + _row_cumsum(is_tie)
        surv = (counts > t) | ((is_tie != 0) & (tie_rank <= quota_ref[...]))
        ties_ref[...] += jnp.sum(is_tie, axis=0, keepdims=True)
        keys_ref[...] = surv.astype(jnp.int32)
        lut_scores(tab_ref, corpus_words(db_ref, score_ref.shape[1]),
                   score_ref, bits, scales_ref)
        keys_ref[...] = jnp.where(keys_ref[...] != 0,
                                  float_key(score_ref[...]), NEG_INF_KEY)
        merge_topk(keys_ref, rk_ref, ri_ref, tile * block_n)

    @pl.when(j == 2 * nt - 1)
    def _finalize():
        ok_ref[...] = rk_ref[...]
        oi_ref[...] = ri_ref[...]


def _fused_scored_call(q_words, q_tables, words_db, valid_words, scales,
                       bits, k, rerank_m, top_k, block_q, block_n,
                       interpret):
    qn, w = q_words.shape
    n = words_db.shape[0]
    fp = q_tables.shape[1]
    assert q_tables.shape[0] == qn, (q_words.shape, q_tables.shape)
    assert w == words_db.shape[1], (q_words.shape, words_db.shape)
    assert fp == w * (32 // bits) * (1 << bits), (q_tables.shape,
                                                  words_db.shape, bits)
    assert rerank_m >= 1 and top_k >= 1, (rerank_m, top_k)
    assert block_n % 32 == 0, block_n
    if scales is not None:
        assert q_tables.dtype == jnp.int8, q_tables.dtype
        assert scales.shape == (qn, w), (scales.shape, qn, w)
    if n == 0:
        return (jnp.full((qn, top_k), _NEG_INF, jnp.float32),
                jnp.full((qn, top_k), -1, jnp.int32))
    qT = _pad(q_words, block_q, 0).T                      # [W, Qp]
    tT = transposed_tables(q_tables, block_q)             # [F*P, Qp]
    dbp = _pad(words_db, block_n, 0)
    qm, nm = qT.shape[1], dbp.shape[0]
    nt = nm // block_n
    t_rows = _round_up(top_k, 8)
    inputs = [qT, tT, dbp]
    in_specs = [
        pl.BlockSpec((w, block_q), lambda i, j: (0, i)),
        pl.BlockSpec((fp, block_q), lambda i, j: (0, i)),
        pl.BlockSpec((block_n, w), lambda i, j: (j % nt, 0)),
    ]
    if valid_words is not None:
        inputs.append(transposed_valid(valid_words, n, nm))
        in_specs.append(
            pl.BlockSpec((block_n // 32, 1), lambda i, j: (j % nt, 0)))
    if scales is not None:
        inputs.append(_pad(scales.astype(jnp.float32), block_q, 0).T)
        in_specs.append(pl.BlockSpec((w, block_q), lambda i, j: (0, i)))
    kernel = functools.partial(
        _fused_scored_kernel, bits=bits, k=k, rerank_m=rerank_m,
        n_valid=n, block_n=block_n, nt=nt,
        has_mask=valid_words is not None, has_scales=scales is not None)
    out_specs, run_scratch = topk_out_specs(t_rows, block_q)
    keys, ids = pl.pallas_call(
        kernel,
        grid=(qm // block_q, 2 * nt),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((t_rows, qm), jnp.int32)] * 2,
        scratch_shapes=[
            pltpu.VMEM((block_n, block_q), jnp.int32),
            pltpu.VMEM((block_n, block_q), jnp.float32),
            pltpu.VMEM((k + 1, block_q), jnp.int32),
            pltpu.VMEM((1, block_q), jnp.int32),
            pltpu.VMEM((1, block_q), jnp.int32),
            pltpu.VMEM((1, block_q), jnp.int32),
        ] + run_scratch,
        interpret=interpret,
    )(*inputs)
    return key_float(keys[:top_k, :qn].T), ids[:top_k, :qn].T


@functools.partial(
    jax.jit,
    static_argnames=("bits", "k", "rerank_m", "top_k", "block_q",
                     "block_n", "interpret"))
def fused_scored_topk_pallas(q_words, q_tables, words_db, bits: int,
                             k: int, rerank_m: int, top_k: int, *,
                             scales=None, block_q: int = 128,
                             block_n: int = 512, interpret: bool = False):
    """Single-pass scored search: q_words uint32 [Q, W], q_tables float
    or int8 [Q, F*P], words_db uint32 [N, W] -> (scores f32 [Q, top_k],
    corpus ids int32 [Q, top_k]).

    Top-``top_k`` by LUT score over the exact stable coarse
    top-``rerank_m`` by collision count, in one streamed pass — no
    [Q, N] matrix, no candidate-id round-trip through HBM. ``scales``
    float32 [Q, W] selects the int8 table path. Bit-exact vs
    ``ref.fused_scored_topk_ref`` (scores, lowest-id ties, (-inf, -1)
    sentinel padding when candidates run out).
    """
    return _fused_scored_call(q_words, q_tables, words_db, None, scales,
                              bits, k, rerank_m, top_k, block_q, block_n,
                              interpret)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "k", "rerank_m", "top_k", "block_q",
                     "block_n", "interpret"))
def fused_scored_topk_masked_pallas(q_words, q_tables, words_db,
                                    valid_words, bits: int, k: int,
                                    rerank_m: int, top_k: int, *,
                                    scales=None, block_q: int = 128,
                                    block_n: int = 512,
                                    interpret: bool = False):
    """``fused_scored_topk_pallas`` over live rows only: ``valid_words``
    uint32 [ceil(N/32)] packed bitmask (``packing.pack_bitmask``
    layout). Tombstoned rows take count -1 before the survivor rule, so
    they can neither survive nor displace a live tie; the mask is data,
    not shape — deletes never recompile. Bit-exact vs
    ``ref.fused_scored_topk_masked_ref``.
    """
    return _fused_scored_call(q_words, q_tables, words_db, valid_words,
                              scales, bits, k, rerank_m, top_k, block_q,
                              block_n, interpret)
