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
    XOR/popcount counts per tile, accumulate A(c) into a [k+1, bq] VMEM
    histogram (tiles are transposed, rows on sublanes and queries on
    lanes, like every streaming kernel here) — but only over the bin
    window [lo, hi) that can still change the inversion. ``hi`` is the
    tile's largest count: bins at or above it gain nothing from the
    tile. ``lo`` is the smallest threshold any valid query lane has so
    far (the least c with A(c) < m over lanes < q_valid; 0 at the first
    tile), kept in SMEM and raised after each tile. Exact: A only grows
    as tiles arrive, so a bin below ``lo`` already held m or more in
    every valid lane when it stopped being updated, and stays >= m both
    as stored and as it truly is — the inversion's ``A(c) < m`` is false
    there either way — while every bin from ``lo`` up is exact. So t,
    A(t) and the quota are those of the full histogram. Padded lanes
    leave ``lo`` alone; their threshold is forced past k anyway. On
    Gaussian-like count spreads the window is a few dozen of the k+1
    bins once a segment's first tiles have set ``lo``; a tile that holds
    a near-duplicate opens it up to that count. At the phase boundary
    (j == NT) the histogram inverts into (t, quota) with a min/max
    reduction — no gather, no sort.

sweep B (j >= NT)
    Recompute the tile's counts (cheaper than writing [Q, N] to HBM and
    reading it back), evaluate the survivor rule — id-ascending tie
    ranks come from a sequential per-query tie counter plus an in-tile
    cumsum, computed as a triangular f32 matmul (MXU-friendly; exact
    below 2^24) — and key every non-survivor -inf. Then LUT-score only
    the 8-row chunks that hold a survivor in some query lane: the
    tile's chunk flags are reduced in one vector pass, and a loop
    visits the flagged chunks alone (about 1.6% of chunks at m = 64
    over 2^22 rows and 128 lanes). A skipped chunk is never scored:
    its keys stay -inf, as the mask made them, and scores are written
    only into the keys of the chunk that computed them, so nothing of
    an unscored chunk is ever read. Merge into the running (scores,
    ids) top-k exactly like ``packed_lut`` — unless no chunk of the
    tile was scored: a tile of -inf keys cannot displace the running
    list, whose equal-keyed entries win ties, so that merge would
    change nothing. The survivors' scores, and the order in which they
    merge, are those of scoring every row.

The kernel counts, per query tile in SMEM scalars, the chunks it
scored and the histogram bins sweep A visited; ``counters=True``
returns both totals, each with the number of all chunks or bins.
Padded query lanes take a threshold no count reaches, so they flag no
chunk.

Scoring paths (``packed_lut.lut_chunk``): float tables upcast to float32
before the kernel and accumulate in (word, field) order (bit-identical
to ``ref.lut_scores_rowwise_ref``); int8 tables take per-(query, word)
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
from repro.kernels.packed_lut import (_LUT_ROWS, corpus_words, lut_chunk,
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
                         n_valid: int, q_valid: int, block_n: int, nt: int,
                         has_mask: bool, has_scales: bool):
    it = iter(refs)
    q_ref, tab_ref, db_ref = next(it), next(it), next(it)
    valid_ref = next(it) if has_mask else None
    scales_ref = next(it) if has_scales else None
    ok_ref, oi_ref, oc_ref = next(it), next(it), next(it)
    keys_ref, above_ref, thr_ref, quota_ref, ties_ref = (
        next(it), next(it), next(it), next(it), next(it))
    rk_ref, ri_ref, scored_ref = next(it), next(it), next(it)
    lo_ref, bins_ref = next(it), next(it)

    i, j = pl.program_id(0), pl.program_id(1)
    tile = jax.lax.rem(j, nt)
    bq = keys_ref.shape[1]

    def tile_counts():
        _tile_counts(q_ref, db_ref, keys_ref, bits=bits, k=k)
        mask_tail(keys_ref, tile * block_n, n_valid, -1)
        if has_mask:
            mask_rows(keys_ref, live_rows(valid_ref), -1)

    @pl.when(j == 0)
    def _init_hist():
        above_ref[...] = jnp.zeros_like(above_ref)
        lo_ref[0, 0] = 0
        bins_ref[0, 0] = 0

    @pl.when(j < nt)
    def _sweep_a():
        tile_counts()

        def bin_(c, carry):
            above_ref[pl.ds(c, 1), :] += jnp.sum(
                (keys_ref[...] > c).astype(jnp.int32), axis=0,
                keepdims=True)
            return carry

        # only bins in [lo, hi) can still move a valid lane's threshold
        lo, hi = lo_ref[0, 0], jnp.max(keys_ref[...])
        jax.lax.fori_loop(lo, hi, bin_, 0)
        bins_ref[0, 0] += jnp.maximum(hi - lo, 0)
        # bins below lo are stale but >= m in every valid lane, so the
        # least c with A(c) < m over valid lanes never falls below lo
        a = above_ref[...]                                # [k+1, bq]
        cidx = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        lane = i * bq + jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
        lo_ref[0, 0] = jnp.min(
            jnp.where((a < rerank_m) & (lane < q_valid), cidx, k))

    @pl.when(j == nt)
    def _invert():
        a = above_ref[...]                                # [k+1, bq]
        below = a < rerank_m          # nonempty: A(k) == 0 < rerank_m
        cidx = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        t = jnp.min(jnp.where(below, cidx, k + 1), axis=0, keepdims=True)
        # padded query lanes take threshold k + 1, which no count
        # reaches: they admit no survivor, so they never open a chunk
        lane = i * bq + jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
        thr_ref[...] = jnp.where(lane < q_valid, t, k + 1)
        # A is non-increasing, so A(t) is the max over satisfied bins
        a_t = jnp.max(jnp.where(below, a, -1), axis=0, keepdims=True)
        quota_ref[...] = rerank_m - a_t
        ties_ref[...] = jnp.zeros_like(ties_ref)
        init_running(rk_ref, ri_ref, NEG_INF_KEY)
        scored_ref[0, 0] = 0

    @pl.when(j >= nt)
    def _sweep_b():
        tile_counts()
        counts = keys_ref[...]
        t = thr_ref[...]                                  # [1, bq]
        is_tie = (counts == t).astype(jnp.int32)
        tie_rank = ties_ref[...] + _row_cumsum(is_tie)
        surv = (counts > t) | ((is_tie != 0) & (tie_rank <= quota_ref[...]))
        ties_ref[...] += jnp.sum(is_tie, axis=0, keepdims=True)
        # survivors hold 0 until scored, everything else the empty key
        keys_ref[...] = jnp.where(surv, 0, NEG_INF_KEY)
        scored0 = scored_ref[0, 0]
        words_at = corpus_words(db_ref, bq)
        # one flag per 8-row chunk and lane, from 8 strided row loads;
        # the loop then visits only the chunks that hold a survivor,
        # with one vector-to-scalar reduction per visit (a check of
        # every chunk costs about 0.2 us each on a TPU v5e, more than
        # the whole scoring of a sparse tile)
        nc = block_n // _LUT_ROWS
        hit = keys_ref[pl.ds(0, nc, stride=_LUT_ROWS), :]
        for r in range(1, _LUT_ROWS):
            hit = jnp.maximum(
                hit, keys_ref[pl.ds(r, nc, stride=_LUT_ROWS), :])
        live = hit > NEG_INF_KEY                          # [nc, bq]
        cidx = jax.lax.broadcasted_iota(jnp.int32, live.shape, 0)

        def next_chunk(after):
            return jnp.min(jnp.where(live & (cidx > after), cidx, nc))

        def score_chunk(c):
            r0 = pl.multiple_of(c * _LUT_ROWS, _LUT_ROWS)
            keys = keys_ref[pl.ds(r0, _LUT_ROWS), :]
            score = lut_chunk(tab_ref, words_at(r0, _LUT_ROWS), bits,
                              scales_ref)
            keys_ref[pl.ds(r0, _LUT_ROWS), :] = jnp.where(
                keys != NEG_INF_KEY, float_key(score), NEG_INF_KEY)
            scored_ref[0, 0] += 1
            return next_chunk(c)

        jax.lax.while_loop(lambda c: c < nc, score_chunk, next_chunk(-1))

        # a tile with no survivor is all empty keys, which the running
        # list's equal-keyed entries outlast: its merge changes nothing
        @pl.when(scored_ref[0, 0] > scored0)
        def _merge():
            merge_topk(keys_ref, rk_ref, ri_ref, tile * block_n)

    @pl.when(j == 2 * nt - 1)
    def _finalize():
        ok_ref[...] = rk_ref[...]
        oi_ref[...] = ri_ref[...]
        row = jax.lax.broadcasted_iota(jnp.int32, oc_ref.shape, 0)
        oc_ref[...] = jnp.where(row == 0, scored_ref[0, 0], bins_ref[0, 0])


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
                jnp.full((qn, top_k), -1, jnp.int32),
                jnp.zeros((4,), jnp.int32))
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
        n_valid=n, q_valid=qn, block_n=block_n, nt=nt,
        has_mask=valid_words is not None, has_scales=scales is not None)
    out_specs, run_scratch = topk_out_specs(t_rows, block_q)
    keys, ids, tallies = pl.pallas_call(
        kernel,
        grid=(qm // block_q, 2 * nt),
        in_specs=in_specs,
        out_specs=out_specs + [
            pl.BlockSpec((2, block_q), lambda i, j: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((t_rows, qm), jnp.int32)] * 2
        + [jax.ShapeDtypeStruct((2, qm), jnp.int32)],
        scratch_shapes=[
            pltpu.VMEM((block_n, block_q), jnp.int32),
            pltpu.VMEM((k + 1, block_q), jnp.int32),
            pltpu.VMEM((1, block_q), jnp.int32),
            pltpu.VMEM((1, block_q), jnp.int32),
            pltpu.VMEM((1, block_q), jnp.int32),
        ] + run_scratch + [pltpu.SMEM((1, 1), jnp.int32)] * 3,
        interpret=interpret,
    )(*inputs)
    # every lane of a query tile carries that tile's counts: chunks
    # scored in row 0, bins visited in row 1
    tiles = qm // block_q
    counters = jnp.stack([jnp.sum(tallies[0, ::block_q]),
                          jnp.int32(tiles * (nm // _LUT_ROWS)),
                          jnp.sum(tallies[1, ::block_q]),
                          jnp.int32(tiles * nt * (k + 1))])
    return key_float(keys[:top_k, :qn].T), ids[:top_k, :qn].T, counters


@functools.partial(
    jax.jit,
    static_argnames=("bits", "k", "rerank_m", "top_k", "block_q",
                     "block_n", "interpret", "counters"))
def fused_scored_topk_pallas(q_words, q_tables, words_db, bits: int,
                             k: int, rerank_m: int, top_k: int, *,
                             scales=None, block_q: int = 128,
                             block_n: int = 512, interpret: bool = False,
                             counters: bool = False):
    """Single-pass scored search: q_words uint32 [Q, W], q_tables float
    or int8 [Q, F*P], words_db uint32 [N, W] -> (scores f32 [Q, top_k],
    corpus ids int32 [Q, top_k]).

    Top-``top_k`` by LUT score over the exact stable coarse
    top-``rerank_m`` by collision count, in one streamed pass — no
    [Q, N] matrix, no candidate-id round-trip through HBM. ``scales``
    float32 [Q, W] selects the int8 table path. Bit-exact vs
    ``ref.fused_scored_topk_ref`` (scores, lowest-id ties, (-inf, -1)
    sentinel padding when candidates run out).

    ``counters=True`` appends int32 [4], each summed over query tiles:
    the 8-row chunks the kernel LUT-scored, all of them (query tiles x
    padded rows / 8), the histogram bins sweep A visited, and all of
    them (query tiles x corpus tiles x (k + 1)).
    """
    out = _fused_scored_call(q_words, q_tables, words_db, None, scales,
                             bits, k, rerank_m, top_k, block_q, block_n,
                             interpret)
    return out if counters else out[:2]


@functools.partial(
    jax.jit,
    static_argnames=("bits", "k", "rerank_m", "top_k", "block_q",
                     "block_n", "interpret", "counters"))
def fused_scored_topk_masked_pallas(q_words, q_tables, words_db,
                                    valid_words, bits: int, k: int,
                                    rerank_m: int, top_k: int, *,
                                    scales=None, block_q: int = 128,
                                    block_n: int = 512,
                                    interpret: bool = False,
                                    counters: bool = False):
    """``fused_scored_topk_pallas`` over live rows only: ``valid_words``
    uint32 [ceil(N/32)] packed bitmask (``packing.pack_bitmask``
    layout). Tombstoned rows take count -1 before the survivor rule, so
    they can neither survive nor displace a live tie; the mask is data,
    not shape — deletes never recompile. Bit-exact vs
    ``ref.fused_scored_topk_masked_ref``; ``counters`` as there.
    """
    out = _fused_scored_call(q_words, q_tables, words_db, valid_words,
                             scales, bits, k, rerank_m, top_k, block_q,
                             block_n, interpret)
    return out if counters else out[:2]
