"""Collision counts directly on bit-packed codes (ANN engine hot loop).

Two kernels over uint32 word arrays (layout of ``kernels.pack_codes``):

``packed_collision_counts_pallas``
    counts[q, n] = #{ fields j < k : code_q[q, j] == code_db[n, j] },
    computed as k - popcount(fold(xor)) entirely in-register — the codes
    are never unpacked to int32 in HBM. Versus ``kernels.collision`` this
    reads 32/b x fewer bytes per pair and replaces the b-bit equality
    compare with one XOR + OR-fold + popcount per word. Tiled
    (bq, bn, bw) with an int32 VMEM accumulator streaming the word axis
    on the minor grid dimension, exactly like a matmul reduction.

``packed_topk_pallas``
    The fused search kernel: streams the corpus axis per query tile,
    keeping a running (values, ids) top-k in VMEM scratch and merging
    each fresh count tile into it (``_merge_topk``). Ties resolve to
    the lowest corpus id — ids match a full-matrix stable top-k
    bit-for-bit. Only the [Q, top_k] result ever reaches HBM; the
    [Q, N] count matrix is never written.

``packed_topk_masked_pallas``
    The streaming top-k kernel with a packed row-validity bitmask (the
    mutable-index tombstone path, ``repro.index``): one uint32 word
    covers 32 corpus rows, the per-tile mask slice is expanded to a row
    mask in-register, and dead rows are forced to -1 before the top-k
    merge — deletes cost one bit of HBM per row and zero recompiles,
    because the mask is data, not shape.

Streaming layout (shared by every top-k kernel of the package and the
packed-linear forward pass): a tile is held *transposed*, corpus rows on
sublanes and queries on lanes — [block_n, block_q]. Each packed word
then enters as one lane-broadcast column of the corpus tile against one
sublane-broadcast row of the transposed queries (``qT`` [W, Q], built by
the wrapper), so no op ever puts the W-word axis on lanes, and the
per-query reductions of the merge run along sublanes. Tiles are
computed in row chunks inside a ``fori_loop`` so the unrolled code stays
small at any block size. The wrappers transpose results back to
[Q, top_k].

Padding: the wrappers zero-pad every axis. Zero-padded words XOR to zero
and contribute no mismatches, so counts stay exact; zero-padded corpus
*rows* would alias a real all-zero code row, so the top-k kernel masks
rows past the static ``n_valid`` count to -1 before merging — that mask
is load-bearing, not belt-and-braces. (The masked kernel folds row
padding into the bitmask itself: bits past N are zeroed by the wrapper.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import bitmask_width, mismatch_count_words

__all__ = ["packed_collision_counts_pallas", "packed_topk_pallas",
           "packed_topk_masked_pallas"]

# corpus rows per in-kernel chunk of the count pass (one validity word)
_COUNT_ROWS = 32
# key of a merged-away entry: below every real key, float -inf included
_TAKEN = -(2 ** 31)


def _mismatch_bits(xor, bits: int):
    """Per-word count of differing b-bit fields, in-register (the shared
    OR-fold + SWAR popcount from the ``core.packing`` oracle — one
    implementation, kernel and oracle can't drift)."""
    return mismatch_count_words(xor, bits).astype(jnp.int32)


def _pad(x, mult, axis, fill=0):
    p = (-x.shape[axis]) % mult
    if p == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, p)
    return jnp.pad(x, widths, constant_values=fill)


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


# -- shared pieces of the transposed streaming layout -------------------------

def float_key(x):
    """float32 -> int32 with the same order (a total order; -0.0 < 0.0,
    -inf above every merged-away ``_TAKEN`` key). Scored kernels merge
    on these keys, so one integer merge serves counts and scores."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0, b ^ jnp.int32(0x7FFFFFFF), b)


def key_float(k):
    """Inverse of ``float_key``."""
    b = jnp.where(k < 0, k ^ jnp.int32(0x7FFFFFFF), k)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _tile_counts(qT_ref, db_ref, keys_ref, *, bits: int, k: int):
    """Collision counts of one corpus tile: qT uint32 [W, L], db uint32
    [bn, W] -> keys_ref int32 [bn, L] (rows on sublanes)."""
    bn, n_words = db_ref.shape
    rows = min(_COUNT_ROWS, bn)
    q = qT_ref[...]

    def chunk(c, carry):
        r0 = pl.multiple_of(c * rows, rows)
        db = db_ref[pl.ds(r0, rows), :]
        mism = jnp.zeros((rows, q.shape[1]), jnp.int32)
        for w in range(n_words):
            mism = mism + _mismatch_bits(
                jnp.bitwise_xor(db[:, w:w + 1], q[w:w + 1, :]), bits)
        keys_ref[pl.ds(r0, rows), :] = k - mism
        return carry

    jax.lax.fori_loop(0, bn // rows, chunk, 0)


def live_rows(valid_ref):
    """Packed validity tile uint32 [bn/32, 1] -> int32 [bn, 1] row mask
    (1 = live). Bit r%32 of word r//32 is row r; each word expands down
    32 sublanes, so no lane-to-sublane reshape is needed."""
    bit = jax.lax.broadcasted_iota(jnp.uint32, (32, 1), 0)
    parts = [((valid_ref[i:i + 1, :] >> bit) & jnp.uint32(1))
             for i in range(valid_ref.shape[0])]
    live = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return live.astype(jnp.int32)


def mask_rows(ref, keep, fill):
    """ref[r, :] = fill wherever ``keep`` [bn, 1] is zero/false."""
    keep = jnp.broadcast_to(keep, ref.shape)
    ref[...] = jnp.where(keep != 0, ref[...], fill)


def mask_tail(ref, row0, n_valid: int, fill):
    """Rows whose global index row0 + r is >= n_valid take ``fill``."""
    rows = jax.lax.broadcasted_iota(jnp.int32, ref.shape, 0) + row0
    ref[...] = jnp.where(rows < n_valid, ref[...], fill)


def init_running(rk_ref, ri_ref, key):
    """Empty running list: every slot (key, id -1)."""
    rk_ref[...] = jnp.full(rk_ref.shape, key, jnp.int32)
    ri_ref[...] = jnp.full(ri_ref.shape, -1, jnp.int32)


def merge_topk(keys_ref, rk_ref, ri_ref, gid0):
    """Merge a tile's keys into the running top list, in place.

    keys_ref int32 [bn, L]: the tile (row r has id gid0 + r; consumed);
    rk_ref/ri_ref int32 [T, L]: the running keys/ids, sorted descending.
    Each of T rounds takes the largest key left and, among equal keys,
    the running list before the tile and the lower row within each —
    the order of a stable top-T over ``concat([running, tile])``, so
    ties keep the lowest corpus id and the running sentinels outlast
    equal-keyed tile rows.
    """
    bn, lanes = keys_ref.shape
    t_rows = rk_ref.shape[0]
    pos_t = jax.lax.broadcasted_iota(jnp.int32, (t_rows, lanes), 0)
    pos_n = jax.lax.broadcasted_iota(jnp.int32, (bn, lanes), 0)
    none = jnp.int32(2 ** 30)

    def one_round(r, carry):
        new_k, new_i = carry
        rk, tk = rk_ref[...], keys_ref[...]
        best = jnp.maximum(jnp.max(rk, axis=0, keepdims=True),
                           jnp.max(tk, axis=0, keepdims=True))
        pr = jnp.min(jnp.where(rk == best, pos_t, none), axis=0,
                     keepdims=True)
        pt = jnp.min(jnp.where(tk == best, pos_n, none), axis=0,
                     keepdims=True)
        from_run = pr < none
        run_id = jnp.max(jnp.where(pos_t == pr, ri_ref[...], _TAKEN),
                         axis=0, keepdims=True)
        pick = jnp.where(from_run, run_id, gid0 + pt)
        new_k = jnp.where(pos_t == r, best, new_k)
        new_i = jnp.where(pos_t == r, pick, new_i)
        rk_ref[...] = jnp.where(from_run & (pos_t == pr), _TAKEN, rk)
        keys_ref[...] = jnp.where(jnp.logical_not(from_run) & (pos_n == pt),
                                  _TAKEN, tk)
        return new_k, new_i

    zeros = jnp.zeros((t_rows, lanes), jnp.int32)
    new_k, new_i = jax.lax.fori_loop(0, t_rows, one_round, (zeros, zeros))
    rk_ref[...] = new_k
    ri_ref[...] = new_i


def transposed_valid(valid_words, n: int, nm: int):
    """Packed bitmask uint32 [ceil(n/32)] -> [nm/32, 1] with the bits
    past n (row padding) zeroed, the kernels' validity operand."""
    nw = bitmask_width(n)
    assert valid_words.shape == (nw,), (valid_words.shape, nw)
    vw = valid_words.astype(jnp.uint32)
    if n % 32:      # zero mask bits past N inside the last partial word
        vw = vw.at[-1].set(vw[-1] & jnp.uint32((1 << (n % 32)) - 1))
    return jnp.pad(vw, (0, nm // 32 - nw)).reshape(nm // 32, 1)


def topk_out_specs(t_rows: int, block_q: int):
    """(out_specs, scratch) of a streaming top-k kernel: the running
    (keys, ids) [T, block_q] pair, written out once per query tile."""
    spec = pl.BlockSpec((t_rows, block_q), lambda i, j: (0, i))
    return [spec, spec], [pltpu.VMEM((t_rows, block_q), jnp.int32),
                          pltpu.VMEM((t_rows, block_q), jnp.int32)]


# -- all-pairs counts ---------------------------------------------------------

def _counts_kernel(q_ref, db_ref, o_ref, acc_ref, *, bits: int, k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]           # [bq, bw] uint32
    db = db_ref[...]         # [bn, bw] uint32
    xor = jnp.bitwise_xor(q[:, None, :], db[None, :, :])
    acc_ref[...] += jnp.sum(_mismatch_bits(xor, bits), axis=-1)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = k - acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("bits", "k", "block_q", "block_n", "block_w",
                     "interpret"))
def packed_collision_counts_pallas(words_q, words_db, bits: int, k: int, *,
                                   block_q: int = 128, block_n: int = 128,
                                   block_w: int = 64,
                                   interpret: bool = False):
    """words_q uint32 [Q, W], words_db uint32 [N, W] -> int32 counts [Q, N].

    Matches ``ref.packed_collision_ref`` bit-exactly, including rows whose
    last word carries zero-padded fields (k < W * 32/bits).
    """
    qn, w = words_q.shape
    n, w2 = words_db.shape
    assert w == w2, (words_q.shape, words_db.shape)
    bw = min(block_w, w)
    qp = _pad(_pad(words_q, block_q, 0), bw, 1)
    dbp = _pad(_pad(words_db, block_n, 0), bw, 1)
    qm, wp = qp.shape
    nm = dbp.shape[0]
    grid = (qm // block_q, nm // block_n, wp // bw)
    out = pl.pallas_call(
        functools.partial(_counts_kernel, bits=bits, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, bw), lambda i, j, s: (i, s)),
            pl.BlockSpec((block_n, bw), lambda i, j, s: (j, s)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qm, nm), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_q, block_n), jnp.int32)],
        interpret=interpret,
    )(qp, dbp)
    return out[:qn, :n]


# -- fused streaming top-k (plain and over live rows only) --------------------

def _topk_kernel(*refs, bits: int, k: int, n_valid: int, block_n: int,
                 has_mask: bool):
    if has_mask:
        qT_ref, db_ref, valid_ref, ok_ref, oi_ref, keys_ref, rk_ref, \
            ri_ref = refs
    else:
        qT_ref, db_ref, ok_ref, oi_ref, keys_ref, rk_ref, ri_ref = refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        init_running(rk_ref, ri_ref, -1)

    _tile_counts(qT_ref, db_ref, keys_ref, bits=bits, k=k)
    if has_mask:
        # dead rows -> -1 before the merge; the wrapper zeroes bits past
        # N, so block row-padding is dead too
        mask_rows(keys_ref, live_rows(valid_ref), -1)
    else:
        mask_tail(keys_ref, j * block_n, n_valid, -1)
    merge_topk(keys_ref, rk_ref, ri_ref, j * block_n)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        ok_ref[...] = rk_ref[...]
        oi_ref[...] = ri_ref[...]


def _topk_call(words_q, words_db, valid_words, bits, k, top_k, block_q,
               block_n, interpret):
    qn, w = words_q.shape
    n = words_db.shape[0]
    assert w == words_db.shape[1], (words_q.shape, words_db.shape)
    assert block_n % 32 == 0, block_n
    qT = _pad(words_q, block_q, 0).T                       # [W, Qp]
    dbp = _pad(words_db, block_n, 0)
    qm, nm = qT.shape[1], dbp.shape[0]
    t_rows = _round_up(top_k, 8)
    inputs = [qT, dbp]
    in_specs = [pl.BlockSpec((w, block_q), lambda i, j: (0, i)),
                pl.BlockSpec((block_n, w), lambda i, j: (j, 0))]
    if valid_words is not None:
        inputs.append(transposed_valid(valid_words, n, nm))
        in_specs.append(
            pl.BlockSpec((block_n // 32, 1), lambda i, j: (j, 0)))
    out_specs, run_scratch = topk_out_specs(t_rows, block_q)
    keys, ids = pl.pallas_call(
        functools.partial(_topk_kernel, bits=bits, k=k, n_valid=n,
                          block_n=block_n,
                          has_mask=valid_words is not None),
        grid=(qm // block_q, nm // block_n),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((t_rows, qm), jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM((block_n, block_q), jnp.int32)]
        + run_scratch,
        interpret=interpret,
    )(*inputs)
    return keys[:top_k, :qn].T, ids[:top_k, :qn].T


@functools.partial(
    jax.jit,
    static_argnames=("bits", "k", "top_k", "block_q", "block_n",
                     "interpret"))
def packed_topk_pallas(words_q, words_db, bits: int, k: int, top_k: int, *,
                       block_q: int = 128, block_n: int = 512,
                       interpret: bool = False):
    """-> (counts [Q, top_k] int32, ids [Q, top_k] int32), streaming the
    corpus axis: HBM traffic is O(Q*W + N*W + Q*top_k), never O(Q*N).

    Rows beyond N (block padding) surface as (-1, -1) only when
    top_k > N. Tie-breaking matches ``ref.packed_topk_ref`` exactly.
    """
    return _topk_call(words_q, words_db, None, bits, k, top_k, block_q,
                      block_n, interpret)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "k", "top_k", "block_q", "block_n",
                     "interpret"))
def packed_topk_masked_pallas(words_q, words_db, valid_words, bits: int,
                              k: int, top_k: int, *, block_q: int = 128,
                              block_n: int = 512, interpret: bool = False):
    """Streaming top-k over rows whose validity bit is set.

    ``valid_words``: uint32 [ceil(N/32)] packed bitmask in the
    ``packing.pack_bitmask`` layout. Dead rows are masked to -1 before
    every merge, so they can never enter the running list; slots beyond
    the live count surface as (-1, -1). Bit-exact (values, tie-broken
    ids) vs ``ref.packed_topk_masked_ref``. The mask is *data* — deletes
    never change any traced shape, so the jit cache entry survives any
    tombstone pattern.
    """
    return _topk_call(words_q, words_db, valid_words, bits, k, top_k,
                      block_q, block_n, interpret)
