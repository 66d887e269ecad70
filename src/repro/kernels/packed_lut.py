"""Fused LUT scoring on bit-packed codes (the re-rank hot loop).

Where ``packed_collision`` ranks by the *diagonal* of the code
contingency table (collision counts), these kernels rank by an arbitrary
per-cell score table (``repro.rank.RankTables``): each b-bit corpus
field selects one of 2^b per-query float entries and the selections
accumulate in float32 — a product-quantization-style asymmetric
distance computation fused with streaming top-k.

Three kernels, all sharing the field loop (``lut_scores``, which runs
``lut_chunk`` over a tile's row chunks) and the running-top-k merge of
``packed_collision``:

``packed_lut_topk_pallas``
    Full-corpus scored search: streams corpus words per query tile,
    accumulates LUT scores in-register (the [Q, N] score matrix never
    reaches HBM), keeps a running (scores, ids) top-k in VMEM scratch.

``packed_lut_topk_masked_pallas``
    Same with a packed row-validity bitmask (tombstoned rows score -inf
    on device; the mask is data, not shape — zero recompiles).

``packed_lut_rerank_pallas``
    The two-stage second pass: per-query *gathered* candidate rows
    [Q, M, W] (from a coarse packed-collision top-m) plus a validity
    matrix, streaming top-k over the candidate axis. Returned ids are
    candidate positions; callers map them through the coarse id list.

Layout: tiles are transposed like every streaming kernel of the package
(rows on sublanes, queries on lanes — see ``packed_collision``). The
tables enter transposed too, [F*P, Q]: the 2^b entries of one field are
2^b consecutive rows, each read as one sublane-broadcast [1, Q] row, so
no lane is ever extracted from a table.

Table lookups are branchless: the 2^b entries of a field's table column
are combined through a depth-b select tree keyed on the field's bits
(``_lut_select``), so the gather is b vectorized selects — no dynamic
indexing in the kernel. Tables may be stored bf16 (``RankTables
.quantize``); they are upcast to float32 before the kernel, so
accumulation is float32 either way and matches the jnp oracle
bit-for-bit.

Padding: query rows pad with zero tables, corpus rows are masked to -inf
past ``n_valid`` (or via the bitmask), candidate slots pad with validity
0 — so padded entries can never beat the running list's -inf/-1 init
(stable ties keep the earlier -1 entries, exactly like the count
kernels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.packed_collision import (
    _pad, _round_up, float_key, init_running, key_float, live_rows,
    mask_rows, mask_tail, merge_topk, topk_out_specs, transposed_valid)

__all__ = ["packed_lut_topk_pallas", "packed_lut_topk_masked_pallas",
           "packed_lut_rerank_pallas"]

# corpus rows per in-kernel chunk of the scoring pass
_LUT_ROWS = 8


# ``float_key(-inf)``: the key of an empty slot
NEG_INF_KEY = -(2 ** 31) + 0x007FFFFF


def _lut_select(c, entries):
    """Branchless 2^b-way table lookup: pick entries[c] per lane.

    c: uint32 field values (any broadcastable shape); entries: list of
    2^b arrays (the field's table column, broadcastable against c).
    A depth-b binary select tree on c's bits; returns entries[c]
    element-wise with no gather.
    """
    level = list(entries)
    bit = 0
    while len(level) > 1:
        b = ((c >> jnp.uint32(bit)) & jnp.uint32(1)) != 0
        level = [jnp.where(b, level[2 * i + 1], level[2 * i])
                 for i in range(len(level) // 2)]
        bit += 1
    return level[0]


def corpus_words(db_ref, lanes: int):
    """``words_at`` for a corpus tile db [bn, W]: rows r0.. as W
    lane-broadcast [rows, lanes] arrays."""
    def words_at(r0, rows):
        db = db_ref[pl.ds(r0, rows), :]
        return [jnp.broadcast_to(db[:, w:w + 1], (rows, lanes))
                for w in range(db_ref.shape[1])]
    return words_at


def lut_chunk(tab_ref, words, bits: int, scales_ref=None):
    """LUT scores f32 [rows, L] of one row chunk: the sum over every
    (word, field) slot of the table row the slot's code selects.

    tab_ref [F*P, L] (f32, or int32-upcast int8 entries with
    ``scales_ref`` f32 [W, L]); ``words``: the chunk's W uint32
    [rows, L] arrays. Float tables accumulate in (word, field) order —
    the order of ``ref.lut_scores_ref`` / ``lut_scores_rowwise_ref``,
    so sums are bit-identical. int8 tables sum each word's 32/b entries
    exactly in int32 and join the float32 total as ``score += scale *
    float(isum)`` in word order (``ref.lut_scores_rowwise_int8_ref``).
    """
    rows, lanes = words[0].shape
    p = 1 << bits
    cpw = 32 // bits
    mask = jnp.uint32(p - 1)
    score = jnp.zeros((rows, lanes), jnp.float32)
    for w, word in enumerate(words):
        # float tables add straight into the score; int8 entries
        # first sum exactly per word
        acc = score if scales_ref is None else jnp.zeros(
            (rows, lanes), jnp.int32)
        for f in range(cpw):
            code = (word >> jnp.uint32(f * bits)) & mask
            base = (w * cpw + f) * p
            acc = acc + _lut_select(
                code, [tab_ref[base + i:base + i + 1, :] for i in range(p)])
        score = acc if scales_ref is None else (
            score + scales_ref[w:w + 1, :] * acc.astype(jnp.float32))
    return score


def lut_scores(tab_ref, words_at, score_ref, bits: int, scales_ref=None):
    """LUT-score a transposed tile chunk by chunk (``lut_chunk``):
    score_ref f32 [bn, L]; ``words_at(r0, rows)`` -> W uint32 [rows, L]
    arrays."""
    bn = score_ref.shape[0]
    rows = min(_LUT_ROWS, bn)

    def chunk(c, carry):
        r0 = pl.multiple_of(c * rows, rows)
        score_ref[pl.ds(r0, rows), :] = lut_chunk(
            tab_ref, words_at(r0, rows), bits, scales_ref)
        return carry

    jax.lax.fori_loop(0, bn // rows, chunk, 0)


def transposed_tables(q_tables, block_q: int):
    """Per-query tables [Q, F*P] -> kernel operand [F*P, Qp]: float
    tables upcast to float32, int8 tables to int32 (exact either way)."""
    dt = jnp.int32 if q_tables.dtype == jnp.int8 else jnp.float32
    return _pad(q_tables.astype(dt), block_q, 0).T


# -- full-corpus scored top-k (plain and over live rows only) -----------------

def _lut_topk_kernel(*refs, bits: int, n_valid: int, block_n: int,
                     has_mask: bool):
    if has_mask:
        tab_ref, db_ref, valid_ref, ok_ref, oi_ref, score_ref, keys_ref, \
            rk_ref, ri_ref = refs
    else:
        tab_ref, db_ref, ok_ref, oi_ref, score_ref, keys_ref, rk_ref, \
            ri_ref = refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        init_running(rk_ref, ri_ref, NEG_INF_KEY)

    lut_scores(tab_ref, corpus_words(db_ref, score_ref.shape[1]), score_ref,
               bits)
    keys_ref[...] = float_key(score_ref[...])
    if has_mask:
        # packed validity tile -> row mask (wrapper zeroes bits > N)
        mask_rows(keys_ref, live_rows(valid_ref), NEG_INF_KEY)
    else:
        mask_tail(keys_ref, j * block_n, n_valid, NEG_INF_KEY)
    merge_topk(keys_ref, rk_ref, ri_ref, j * block_n)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        ok_ref[...] = rk_ref[...]
        oi_ref[...] = ri_ref[...]


def _lut_topk_call(q_tables, words_db, valid_words, bits, top_k, block_q,
                   block_n, interpret):
    qn, fp = q_tables.shape
    n, w = words_db.shape
    assert fp == w * (32 // bits) * (1 << bits), (q_tables.shape,
                                                  words_db.shape, bits)
    assert block_n % 32 == 0, block_n
    tT = transposed_tables(q_tables, block_q)
    dbp = _pad(words_db, block_n, 0)
    qm, nm = tT.shape[1], dbp.shape[0]
    t_rows = _round_up(top_k, 8)
    inputs = [tT, dbp]
    in_specs = [pl.BlockSpec((fp, block_q), lambda i, j: (0, i)),
                pl.BlockSpec((block_n, w), lambda i, j: (j, 0))]
    if valid_words is not None:
        inputs.append(transposed_valid(valid_words, n, nm))
        in_specs.append(
            pl.BlockSpec((block_n // 32, 1), lambda i, j: (j, 0)))
    out_specs, run_scratch = topk_out_specs(t_rows, block_q)
    keys, ids = pl.pallas_call(
        functools.partial(_lut_topk_kernel, bits=bits, n_valid=n,
                          block_n=block_n,
                          has_mask=valid_words is not None),
        grid=(qm // block_q, nm // block_n),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((t_rows, qm), jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM((block_n, block_q), jnp.float32),
                        pltpu.VMEM((block_n, block_q), jnp.int32)]
        + run_scratch,
        interpret=interpret,
    )(*inputs)
    return key_float(keys[:top_k, :qn].T), ids[:top_k, :qn].T


@functools.partial(
    jax.jit,
    static_argnames=("bits", "top_k", "block_q", "block_n", "interpret"))
def packed_lut_topk_pallas(q_tables, words_db, bits: int, top_k: int, *,
                           block_q: int = 128, block_n: int = 512,
                           interpret: bool = False):
    """q_tables float [Q, F*P] (``rank.RankTables.query_tables``),
    words_db uint32 [N, W] -> (scores f32 [Q, top_k], ids int32
    [Q, top_k]), streaming the corpus axis (HBM traffic O(Q*F*P + N*W +
    Q*top_k), never O(Q*N)).

    Bit-exact (scores and lowest-id tie-breaks) vs
    ``ref.packed_lut_topk_ref``; empty slots surface as (-inf, -1).
    """
    return _lut_topk_call(q_tables, words_db, None, bits, top_k, block_q,
                          block_n, interpret)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "top_k", "block_q", "block_n", "interpret"))
def packed_lut_topk_masked_pallas(q_tables, words_db, valid_words,
                                  bits: int, top_k: int, *,
                                  block_q: int = 128, block_n: int = 512,
                                  interpret: bool = False):
    """Scored streaming top-k over rows whose validity bit is set.

    ``valid_words``: uint32 [ceil(N/32)] bitmask (``packing
    .pack_bitmask`` layout). Dead rows score -inf on device and never
    enter the running list; slots beyond the live count surface as
    (-inf, -1). Bit-exact vs ``ref.packed_lut_topk_masked_ref``. The
    mask is data — tombstone patterns never trigger a recompile.
    """
    return _lut_topk_call(q_tables, words_db, valid_words, bits, top_k,
                          block_q, block_n, interpret)


# -- per-query candidate re-rank (two-stage second pass) ----------------------

def _lut_rerank_kernel(tab_ref, cand_ref, valid_ref, ok_ref, oi_ref,
                       score_ref, keys_ref, rk_ref, ri_ref, *, bits: int,
                       block_m: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        init_running(rk_ref, ri_ref, NEG_INF_KEY)

    def words_at(r0, rows):          # candidates are per query already
        return [cand_ref[w, pl.ds(r0, rows), :]
                for w in range(cand_ref.shape[0])]

    lut_scores(tab_ref, words_at, score_ref, bits)
    keys_ref[...] = jnp.where(valid_ref[...] != 0,
                              float_key(score_ref[...]), NEG_INF_KEY)
    merge_topk(keys_ref, rk_ref, ri_ref, j * block_m)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        ok_ref[...] = rk_ref[...]
        oi_ref[...] = ri_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("bits", "top_k", "block_q", "block_m", "interpret"))
def packed_lut_rerank_pallas(q_tables, cand_words, cand_valid, bits: int,
                             top_k: int, *, block_q: int = 128,
                             block_m: int = 256, interpret: bool = False):
    """Re-rank per-query candidates: q_tables [Q, F*P], cand_words
    uint32 [Q, M, W] (coarse-stage gather), cand_valid int32/bool
    [Q, M] -> (scores f32 [Q, top_k], positions int32 [Q, top_k]).

    Positions index the candidate axis; invalid candidates score -inf
    and surface as (-inf, -1). Streams the M axis with the running
    top-k in VMEM — the [Q, M] score matrix never reaches HBM.
    Bit-exact vs ``ref.packed_lut_rerank_ref``.
    """
    qn, fp = q_tables.shape
    n_q, m, w = cand_words.shape
    assert n_q == qn and cand_valid.shape == (qn, m), (
        q_tables.shape, cand_words.shape, cand_valid.shape)
    assert fp == w * (32 // bits) * (1 << bits), (q_tables.shape,
                                                  cand_words.shape, bits)
    assert block_m % 8 == 0, block_m
    tT = transposed_tables(q_tables, block_q)
    # candidate words [W, Mp, Qp] and validity [Mp, Qp]: transposed tiles
    cw = jnp.transpose(_pad(_pad(cand_words, block_q, 0), block_m, 1),
                       (2, 1, 0))
    cv = _pad(_pad(cand_valid.astype(jnp.int32), block_q, 0),
              block_m, 1).T
    mm, qm = cv.shape
    t_rows = _round_up(top_k, 8)
    out_specs, run_scratch = topk_out_specs(t_rows, block_q)
    keys, ids = pl.pallas_call(
        functools.partial(_lut_rerank_kernel, bits=bits, block_m=block_m),
        grid=(qm // block_q, mm // block_m),
        in_specs=[
            pl.BlockSpec((fp, block_q), lambda i, j: (0, i)),
            pl.BlockSpec((w, block_m, block_q), lambda i, j: (0, j, i)),
            pl.BlockSpec((block_m, block_q), lambda i, j: (j, i)),
        ],
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((t_rows, qm), jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM((block_m, block_q), jnp.float32),
                        pltpu.VMEM((block_m, block_q), jnp.int32)]
        + run_scratch,
        interpret=interpret,
    )(tT, cw, cv)
    return key_float(keys[:top_k, :qn].T), ids[:top_k, :qn].T
