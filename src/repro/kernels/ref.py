"""Pure-jnp oracles for every Pallas kernel in this package.

These define the semantics; kernels must match them bit-for-bit (integer
outputs) across the shared scheme x shape x dtype x mask grid in
tests/test_kernel_conformance.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import packing as _packing
from repro.core import schemes as _schemes
from repro.core.schemes import CodeSpec

__all__ = ["coded_project_ref", "pack_codes_ref", "code_pack_ref",
           "encode_fused_ref", "collision_counts_ref",
           "packed_collision_ref", "packed_topk_ref",
           "packed_topk_masked_ref", "topk_blocked_ref", "topk_stable_ref",
           "lut_scores_ref", "lut_scores_rowwise_ref",
           "lut_scores_rowwise_int8_ref", "topk_scored_ref",
           "packed_lut_topk_ref", "packed_lut_topk_masked_ref",
           "packed_lut_rerank_ref", "packed_linear_fwd_ref",
           "packed_linear_fwd_masked_ref", "packed_linear_bwd_ref",
           "packed_linear_bwd_masked_ref", "coarse_survivor_mask_ref",
           "fused_scored_topk_ref", "fused_scored_topk_masked_ref",
           "two_stage_scored_ref", "two_stage_scored_masked_ref"]


def coded_project_ref(x, r, spec: CodeSpec, q=None):
    """x [M, D] @ r [D, K] -> int32 codes [M, K] under ``spec``.

    The matmul accumulates in float32 regardless of input dtype (matches
    the kernel's MXU accumulator).
    """
    z = jnp.dot(x, r, preferred_element_type=jnp.float32)
    return _schemes.encode(z, spec, q)


def pack_codes_ref(codes, bits: int):
    """codes int [M, K] -> uint32 words [M, ceil(K/(32/bits))]."""
    return _packing.pack_codes(codes, bits)


def code_pack_ref(z, spec: CodeSpec, q=None):
    """Projected z [M, K] float -> packed uint32 [M, ceil(K·b/32)].

    Coding scheme + b-bit pack (the fused-encode epilogue); the oracle
    for ``encode_fused.code_pack_pallas``, bit-exact including the
    zero-padded fields past K."""
    return _packing.pack_codes(
        _schemes.encode(jnp.asarray(z, jnp.float32), spec, q), spec.bits)


def encode_fused_ref(x, r, spec: CodeSpec, q=None):
    """x [M, D] @ r [D, K] -> packed uint32 [M, ceil(K·b/32)].

    Full fused-ingest oracle: f32-accumulated projection, coding under
    ``spec``, bit-pack — the semantics contract of
    ``encode_fused.encode_fused_pallas`` (packed words bit-exact)."""
    return code_pack_ref(
        jnp.dot(x, r, preferred_element_type=jnp.float32), spec, q)


def collision_counts_ref(codes_q, codes_db):
    """codes_q [Q, K], codes_db [N, K] -> int32 [Q, N] match counts."""
    eq = (codes_q[:, None, :] == codes_db[None, :, :])
    return jnp.sum(eq, axis=-1).astype(jnp.int32)


def packed_collision_ref(words_q, words_db, bits: int, k: int):
    """words_q uint32 [Q, W], words_db uint32 [N, W] -> int32 [Q, N].

    All-pairs b-bit collision counts computed directly on packed words
    (XOR + field fold + popcount; semantics in ``packing.match_count_packed``).
    Accumulates word-by-word so the [Q, N] temporaries stay 2-D — the
    broadcast [Q, N, W] intermediate never materializes (W is small and
    static, so the unrolled loop fuses under jit).
    """
    q, w = words_q.shape
    n = words_db.shape[0]
    mism = jnp.zeros((q, n), jnp.int32)
    for j in range(w):
        xor = jnp.bitwise_xor(words_q[:, None, j], words_db[None, :, j])
        mism = mism + _packing.mismatch_count_words(xor, bits).astype(jnp.int32)
    return k - mism


def topk_blocked_ref(m, top_k: int, block: int = 4096):
    """Stable descending top-k over the last axis of int matrix [c, n].

    Bit-identical to ``jax.lax.top_k`` (ties -> lowest index) but built
    for small top_k on large n: one block-max pass over the matrix, then
    per-pick work touches only the winning block, so the cost is
    O(c*n + top_k * c * block) instead of XLA's full per-row sort.
    ~30x faster than ``lax.top_k`` on CPU at [256, 100k], top_k=10.

    Unlike ``lax.top_k``, top_k > n is allowed: overflow slots return the
    dtype-min sentinel as value (ids point past n) — callers mask on
    value < real-minimum.
    """
    c, n = m.shape
    sent = jnp.iinfo(m.dtype).min
    pad = (-max(n, top_k)) % block + (max(n, top_k) - n)
    if pad:
        m = jnp.pad(m, ((0, 0), (0, pad)), constant_values=sent)
    nb = m.shape[1] // block
    mb = m.reshape(c, nb, block)
    bmax = jnp.max(mb, axis=2)                       # [c, nb]
    rows = jnp.arange(c)
    vals, ids = [], []
    for _ in range(top_k):
        b = jnp.argmax(bmax, axis=1)                 # lowest block on ties
        blk = mb[rows, b]                            # [c, block]
        inner = jnp.argmax(blk, axis=1)
        vals.append(blk[rows, inner])
        ids.append((b * block + inner).astype(jnp.int32))
        mb = mb.at[rows, b, inner].set(sent)
        bmax = bmax.at[rows, b].set(jnp.max(mb[rows, b], axis=1))
    return jnp.stack(vals, axis=1), jnp.stack(ids, axis=1)


def topk_stable_ref(m, top_k: int):
    """Stable descending top-k of int scores [c, n] with -1-fill overflow.

    The shared selection for search paths: blocked picking for small
    top_k (fast on CPU), one lax.top_k call beyond that (the unrolled
    pick loop would trace top_k scatter steps). top_k > n is allowed —
    overflow slots come back as (-1, -1); negative scores also surface
    ids of -1 (search paths use negatives to mark non-candidates).
    """
    if top_k > m.shape[1]:
        m = jnp.pad(m, ((0, 0), (0, top_k - m.shape[1])),
                    constant_values=-1)
    if top_k <= 64:
        vals, ids = topk_blocked_ref(m, top_k)
    else:
        vals, ids = jax.lax.top_k(m, top_k)
        ids = ids.astype(jnp.int32)
    return vals, jnp.where(vals < 0, -1, ids)


def packed_topk_ref(words_q, words_db, bits: int, k: int, top_k: int):
    """-> (counts [Q, top_k], ids [Q, top_k]): full packed collision matrix
    followed by a stable descending top-k (lowest corpus id wins ties).

    top_k > N yields (-1, -1) in the overflow slots, matching the
    streaming kernel's scratch-fill semantics.
    """
    counts = packed_collision_ref(words_q, words_db, bits, k)
    return topk_stable_ref(counts, top_k)


# -- LUT-scored ranking (repro.rank) ------------------------------------------

def lut_scores_ref(q_tables, words_db, bits: int):
    """LUT scores on packed words: [Q, F*P] x [N, W] -> float32 [Q, N].

    q_tables is the flat per-query table of ``rank.RankTables
    .query_tables`` (any float dtype; F = W * 32/bits field slots, P =
    2**bits entries each); entry (w*cpw + f)*P + c scores corpus code
    value c at field f of word w. Scores accumulate in float32 field by
    field in (word, field) order — the exact accumulation order of the
    fused kernel, so kernel outputs match bit-for-bit. Padded field
    slots hold zeros, so rows with k < F real codes score correctly.
    """
    p = 1 << bits
    cpw = 32 // bits
    n_words = words_db.shape[-1]
    assert q_tables.shape[-1] == n_words * cpw * p, (
        q_tables.shape, words_db.shape, bits)
    tab = q_tables.astype(jnp.float32)
    score = jnp.zeros((q_tables.shape[0], words_db.shape[0]), jnp.float32)
    for w in range(n_words):
        word = words_db[:, w]
        for f in range(cpw):
            c = (word >> jnp.uint32(f * bits)) & jnp.uint32(p - 1)
            col = (w * cpw + f) * p
            score = score + jnp.take(tab[:, col:col + p],
                                     c.astype(jnp.int32), axis=1)
    return score


def lut_scores_rowwise_ref(q_tables, cand_words, bits: int):
    """Row-wise LUT scores: [Q, F*P] x per-query candidates [Q, M, W]
    -> float32 [Q, M] (same table layout and float32 accumulation order
    as ``lut_scores_ref``; query i scores only its own candidate rows).
    """
    p = 1 << bits
    cpw = 32 // bits
    n_words = cand_words.shape[-1]
    assert q_tables.shape[-1] == n_words * cpw * p, (
        q_tables.shape, cand_words.shape, bits)
    tab = q_tables.astype(jnp.float32)
    score = jnp.zeros(cand_words.shape[:-1], jnp.float32)
    for w in range(n_words):
        word = cand_words[..., w]
        for f in range(cpw):
            c = (word >> jnp.uint32(f * bits)) & jnp.uint32(p - 1)
            col = (w * cpw + f) * p
            score = score + jnp.take_along_axis(
                tab[:, col:col + p], c.astype(jnp.int32), axis=1)
    return score


def topk_scored_ref(scores, top_k: int):
    """Stable descending top-k of float scores [c, n] -> (float32
    [c, top_k], int32 ids [c, top_k]).

    -inf marks non-candidates/empty slots; such slots (and overflow when
    top_k > n) surface as (-inf, -1). Ties resolve to the lowest index
    (``lax.top_k`` is stable), matching the streaming kernels.
    """
    scores = jnp.asarray(scores, jnp.float32)
    if top_k > scores.shape[1]:
        scores = jnp.pad(scores, ((0, 0), (0, top_k - scores.shape[1])),
                         constant_values=-jnp.inf)
    vals, ids = jax.lax.top_k(scores, top_k)
    return vals, jnp.where(jnp.isneginf(vals), -1, ids.astype(jnp.int32))


def packed_lut_topk_ref(q_tables, words_db, bits: int, top_k: int):
    """Full-corpus LUT-scored search: -> (scores f32 [Q, top_k], ids
    int32 [Q, top_k]); the oracle for the fused streaming kernel
    (``packed_lut.packed_lut_topk_pallas``), bit-exact including float
    accumulation order and lowest-id tie-breaks."""
    return topk_scored_ref(lut_scores_ref(q_tables, words_db, bits), top_k)


def packed_lut_topk_masked_ref(q_tables, words_db, valid_words, bits: int,
                               top_k: int):
    """``packed_lut_topk_ref`` over live rows only: ``valid_words`` is
    the packed row-validity bitmask (``packing.pack_bitmask`` layout).
    Dead rows score -inf and never surface; empty slots are (-inf, -1).
    """
    scores = lut_scores_ref(q_tables, words_db, bits)
    live = _packing.unpack_bitmask(valid_words, words_db.shape[0])
    return topk_scored_ref(jnp.where(live[None, :], scores, -jnp.inf),
                           top_k)


def packed_lut_rerank_ref(q_tables, cand_words, cand_valid, bits: int,
                          top_k: int):
    """Per-query candidate re-rank: q_tables [Q, F*P], gathered
    candidate rows [Q, M, W] uint32, cand_valid bool/int [Q, M] ->
    (scores f32 [Q, top_k], positions int32 [Q, top_k]).

    Positions index the candidate axis (0..M-1), NOT corpus rows —
    callers map them through their candidate id list. Invalid candidates
    (coarse-stage -1 slots) score -inf; empty slots are (-inf, -1).
    """
    scores = lut_scores_rowwise_ref(q_tables, cand_words, bits)
    scores = jnp.where(jnp.asarray(cand_valid) != 0, scores, -jnp.inf)
    return topk_scored_ref(scores, top_k)


# -- packed linear classifier (repro.learn) -----------------------------------

def packed_linear_fwd_ref(tables, words_db, bits: int):
    """Margins of a packed linear model: class weight tables float
    [C, F*P] (flat ``learn.features`` layout) × packed words uint32
    [N, W] -> float32 [C, N].

    Identical semantics to ``lut_scores_ref`` with the per-query tables
    replaced by per-class weight tables: margin[c, n] sums, in (word,
    field) order, the table entry each b-bit field of row n selects.
    The oracle for ``packed_linear.packed_linear_fwd_pallas``
    (bit-exact, including float accumulation order).
    """
    return lut_scores_ref(tables, words_db, bits)


def packed_linear_fwd_masked_ref(tables, words_db, valid_words, bits: int):
    """``packed_linear_fwd_ref`` over live rows only: ``valid_words`` is
    the packed row-validity bitmask (``packing.pack_bitmask`` layout);
    dead rows emit margin 0.0 (callers also drop them from the loss)."""
    scores = lut_scores_ref(tables, words_db, bits)
    live = _packing.unpack_bitmask(valid_words, words_db.shape[0])
    return jnp.where(live[None, :], scores, 0.0)


def _onehot_rows(words, bits: int):
    """Dense one-hot of every field slot: uint32 [n, W] -> float32
    [n, F*P] in the flat table layout (phantom field slots included)."""
    p = 1 << bits
    f = words.shape[-1] * (32 // bits)
    codes = _packing.unpack_codes(words, bits, f)          # [n, F]
    hot = codes[..., None] == jnp.arange(p, dtype=jnp.int32)
    return hot.reshape(words.shape[0], f * p).astype(jnp.float32)


def packed_linear_bwd_ref(g, words_db, bits: int, *, block_c: int = 8,
                          block_n: int = 512):
    """Weight-table gradients: upstream margin gradients g float32
    [C, N] × packed words [N, W] -> float32 [C, F*P].

    dTables[c, f*P + v] = sum over rows n whose field f holds code v of
    g[c, n]. The accumulation order is the contract with the fused
    kernel (``packed_linear.packed_linear_bwd_pallas``): rows are
    processed in ``block_n`` chunks (g zero-padded, classes padded to
    ``block_c``, matching the kernel's tile shapes exactly), each chunk
    enters through one one-hot matmul, and chunk results add
    sequentially — bit-exact at equal block sizes.
    """
    c, n = g.shape
    g = jnp.asarray(g, jnp.float32)
    pad_c, pad_n = (-c) % block_c, (-n) % block_n
    if pad_c or pad_n:
        g = jnp.pad(g, ((0, pad_c), (0, pad_n)))
    if pad_n:
        words_db = jnp.pad(words_db, ((0, pad_n), (0, 0)))
    p = 1 << bits
    fp = words_db.shape[1] * (32 // bits) * p
    acc = jnp.zeros((g.shape[0], fp), jnp.float32)
    for lo in range(0, g.shape[1], block_n):
        hot = _onehot_rows(words_db[lo:lo + block_n], bits)
        # the barrier keeps a compiler from folding the running sum into
        # the matmul's accumulator (TPU XLA does under jit), which would
        # change the order of the float additions this oracle fixes
        acc = acc + jax.lax.optimization_barrier(
            jnp.dot(g[:, lo:lo + block_n], hot,
                    preferred_element_type=jnp.float32))
    return acc[:c]


def packed_linear_bwd_masked_ref(g, words_db, valid_words, bits: int, *,
                                 block_c: int = 8, block_n: int = 512):
    """``packed_linear_bwd_ref`` over live rows only: gradient columns
    of rows whose validity bit is clear are zeroed before the scatter,
    so tombstoned examples contribute exactly nothing."""
    live = _packing.unpack_bitmask(valid_words, words_db.shape[0])
    g = jnp.where(live[None, :], jnp.asarray(g, jnp.float32), 0.0)
    return packed_linear_bwd_ref(g, words_db, bits, block_c=block_c,
                                 block_n=block_n)


def packed_topk_masked_ref(words_q, words_db, valid_words, bits: int, k: int,
                           top_k: int):
    """``packed_topk_ref`` over live rows only: ``valid_words`` is the
    packed row-validity bitmask (``packing.pack_bitmask`` layout, bit
    r%32 of word r//32 = row r live). Dead rows never surface — slots
    beyond the live count come back as (-1, -1), exactly as if the store
    held just the live rows (tie order among survivors is unchanged).
    """
    counts = packed_collision_ref(words_q, words_db, bits, k)
    live = _packing.unpack_bitmask(valid_words, words_db.shape[0])
    return topk_stable_ref(jnp.where(live[None, :], counts, -1), top_k)


# -- single-pass fused scored search ------------------------------------------

def lut_scores_rowwise_int8_ref(q_tables, scales, cand_words, bits: int):
    """Row-wise LUT scores from int8 tables with per-(query, word)
    scales: q_tables int8 [Q, F*P], scales float32 [Q, W], cand_words
    [Q, M, W] -> float32 [Q, M].

    The int8 accumulation contract (shared with the fused kernel): the
    32/b selected entries of one packed word sum exactly in int32, then
    each word's integer sum joins the float32 total as
    ``score += scale[q, w] * float(isum)`` in word order. Scales must be
    powers of two (``rank.RankTables.query_tables_int8`` produces them
    that way): the multiply is then exact, so whether a compiler
    contracts it into an FMA or not cannot change a single bit — kernel
    and oracle agree bit-for-bit.
    """
    p = 1 << bits
    cpw = 32 // bits
    n_words = cand_words.shape[-1]
    assert q_tables.shape[-1] == n_words * cpw * p, (
        q_tables.shape, cand_words.shape, bits)
    assert scales.shape == (q_tables.shape[0], n_words), (
        scales.shape, q_tables.shape, cand_words.shape)
    tab = q_tables.astype(jnp.int32)
    score = jnp.zeros(cand_words.shape[:-1], jnp.float32)
    for w in range(n_words):
        word = cand_words[..., w]
        isum = jnp.zeros(cand_words.shape[:-1], jnp.int32)
        for f in range(cpw):
            c = (word >> jnp.uint32(f * bits)) & jnp.uint32(p - 1)
            col = (w * cpw + f) * p
            isum = isum + jnp.take_along_axis(
                tab[:, col:col + p], c.astype(jnp.int32), axis=1)
        score = score + scales[:, w][:, None] * isum.astype(jnp.float32)
    return score


def coarse_survivor_mask_ref(counts, k: int, rerank_m: int):
    """Membership mask [Q, N] bool of the stable coarse top-``rerank_m``
    by collision count (ties -> lowest corpus id), without sorting.

    This is the survivor rule the fused kernel evaluates in-VMEM: with
    t(q) the smallest threshold in [0, k] such that fewer than rerank_m
    rows satisfy count > t (found by binary search — counts live in
    [-1, k]), row n survives iff count > t, or count == t and its
    id-ascending rank among the count == t ties fits the remaining
    quota. Rows with count < 0 (tombstoned / padded) never survive.
    The surviving id set equals ``topk_stable_ref(counts, rerank_m)``'s
    non-sentinel ids exactly.
    """
    q, n = counts.shape
    m = jnp.int32(rerank_m)
    lo = jnp.zeros((q, 1), jnp.int32)
    hi = jnp.full((q, 1), k, jnp.int32)
    for _ in range(max(1, (k + 1).bit_length())):
        mid = (lo + hi) >> 1
        above = jnp.sum((counts > mid).astype(jnp.int32), axis=1,
                        keepdims=True)
        done = above < m
        lo = jnp.where(done, lo, mid + 1)
        hi = jnp.where(done, mid, hi)
    t = lo                                                     # [Q, 1]
    above_t = jnp.sum((counts > t).astype(jnp.int32), axis=1,
                      keepdims=True)
    quota = m - above_t
    is_tie = counts == t
    tie_rank = jnp.cumsum(is_tie.astype(jnp.int32), axis=1)
    return (counts > t) | (is_tie & (tie_rank <= quota))


def _compact_survivors(sm, rerank_m: int):
    """Survivor mask [Q, N] -> id-ascending candidate ids [Q, rerank_m]
    (-1 padded) — no per-row sort. The j-th survivor of a row is the
    first index where the mask's running cumsum reaches j+1, i.e. a
    per-row ``searchsorted`` into the (non-decreasing) cumsum: O(m log
    n) gathers instead of the O(n) scatter this used to be (XLA lowers
    row scatters catastrophically on CPU — ~60x slower than the binary
    searches at the bench shape)."""
    q, n = sm.shape
    csum = jnp.cumsum(sm.astype(jnp.int32), axis=1)            # [Q, N]
    targets = jnp.arange(1, rerank_m + 1, dtype=jnp.int32)     # [m]
    pos = jax.vmap(
        lambda c: jnp.searchsorted(c, targets, side="left"))(csum)
    found = targets[None, :] <= csum[:, -1:]                   # [Q, m]
    return jnp.where(found, pos.astype(jnp.int32), -1)


def _score_candidates(q_tables, words_db, cand, bits: int, top_k: int,
                      scales):
    """Gather candidate rows, LUT-score them (f32 or int8 path), top-k by
    score; -1 candidate slots score -inf. Returns (scores, corpus ids)."""
    n = words_db.shape[0]
    m = cand.shape[1]
    cand_words = jnp.take(words_db, jnp.clip(cand, 0, n - 1), axis=0)
    if scales is None:
        s = lut_scores_rowwise_ref(q_tables, cand_words, bits)
    else:
        s = lut_scores_rowwise_int8_ref(q_tables, scales, cand_words, bits)
    s = jnp.where(cand >= 0, s, -jnp.inf)
    vals, pos = topk_scored_ref(s, top_k)
    ids = jnp.take_along_axis(cand, jnp.clip(pos, 0, m - 1), axis=1)
    return vals, jnp.where(pos < 0, -1, ids)


def _empty_scored(q: int, top_k: int):
    return (jnp.full((q, top_k), -jnp.inf, jnp.float32),
            jnp.full((q, top_k), -1, jnp.int32))


def fused_scored_topk_ref(q_words, q_tables, words_db, bits: int, k: int,
                          rerank_m: int, top_k: int, scales=None):
    """Single-pass scored search oracle: q_words uint32 [Q, W], q_tables
    float/int8 [Q, F*P], words_db uint32 [N, W] -> (scores f32
    [Q, top_k], corpus ids int32 [Q, top_k]).

    Semantics (the contract with ``fused_scored.fused_scored_topk_
    pallas``): survivors are the exact stable coarse top-``rerank_m`` by
    collision count; the result is the top-``top_k`` of the survivors'
    LUT scores, ties -> lowest corpus id. ``scales`` float32 [Q, W]
    selects the int8 path (``lut_scores_rowwise_int8_ref``); otherwise
    tables upcast to float32. Sentinel padding: slots beyond the
    survivor count are (-inf, -1), so rerank_m or top_k larger than the
    corpus degrade exactly like the two-stage path.

    Unlike the two-stage composition this never sorts the [Q, N] count
    matrix — the threshold binary search plus a cumsum compaction is
    O(N log k) per query, which is where the CPU-path speedup over the
    old O(N·rerank_m) coarse ``lax.top_k`` comes from.
    """
    if words_db.shape[0] == 0:
        return _empty_scored(q_words.shape[0], top_k)
    counts = packed_collision_ref(q_words, words_db, bits, k)
    sm = coarse_survivor_mask_ref(counts, k, rerank_m)
    cand = _compact_survivors(sm, rerank_m)
    return _score_candidates(q_tables, words_db, cand, bits, top_k, scales)


def fused_scored_topk_masked_ref(q_words, q_tables, words_db, valid_words,
                                 bits: int, k: int, rerank_m: int,
                                 top_k: int, scales=None):
    """``fused_scored_topk_ref`` over live rows only (``valid_words``:
    packed bitmask, ``packing.pack_bitmask`` layout). Tombstoned rows
    take count -1 before the coarse threshold, so they can neither
    survive nor displace a live tie — all-dead segments return pure
    sentinels."""
    if words_db.shape[0] == 0:
        return _empty_scored(q_words.shape[0], top_k)
    counts = packed_collision_ref(q_words, words_db, bits, k)
    live = _packing.unpack_bitmask(valid_words, words_db.shape[0])
    counts = jnp.where(live[None, :], counts, -1)
    sm = coarse_survivor_mask_ref(counts, k, rerank_m)
    cand = _compact_survivors(sm, rerank_m)
    return _score_candidates(q_tables, words_db, cand, bits, top_k, scales)


def two_stage_scored_ref(q_words, q_tables, words_db, bits: int, k: int,
                         rerank_m: int, top_k: int):
    """The literal two-stage composition (coarse ``packed_topk_ref`` ->
    gather -> ``packed_lut_rerank_ref``), as the engines ran it before
    fusion. The differential oracle for the fused path: identical
    results whenever LUT scores don't tie across different collision
    counts (always true for monotone sign-scheme tables; a measure-zero
    event for generic float tables)."""
    n = words_db.shape[0]
    if n == 0:
        return _empty_scored(q_words.shape[0], top_k)
    cv, ci = packed_topk_ref(q_words, words_db, bits, k, rerank_m)
    vals, pos = packed_lut_rerank_ref(
        q_tables, jnp.take(words_db, jnp.clip(ci, 0, n - 1), axis=0),
        ci >= 0, bits, top_k)
    ids = jnp.take_along_axis(ci, jnp.clip(pos, 0, rerank_m - 1), axis=1)
    return vals, jnp.where(pos < 0, -1, ids)


def two_stage_scored_masked_ref(q_words, q_tables, words_db, valid_words,
                                bits: int, k: int, rerank_m: int,
                                top_k: int):
    """Masked two-stage composition (coarse ``packed_topk_masked_ref``
    -> gather -> re-rank) — the differential oracle for
    ``fused_scored_topk_masked_ref`` under random tombstone masks."""
    n = words_db.shape[0]
    if n == 0:
        return _empty_scored(q_words.shape[0], top_k)
    cv, ci = packed_topk_masked_ref(q_words, words_db, valid_words, bits,
                                    k, rerank_m)
    vals, pos = packed_lut_rerank_ref(
        q_tables, jnp.take(words_db, jnp.clip(ci, 0, n - 1), axis=0),
        ci >= 0, bits, top_k)
    ids = jnp.take_along_axis(ci, jnp.clip(pos, 0, rerank_m - 1), axis=1)
    return vals, jnp.where(pos < 0, -1, ids)
