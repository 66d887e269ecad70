"""Fused linear-classifier kernels directly on bit-packed codes.

The paper's SVM application (§6, Figs 11–14) trains L2 linear models on
the one-hot expansion of the codes: k projections × 2^b code values per
row, exactly k ones. Materializing that [N, k·2^b] float matrix is a
32/b × 2^b blow-up over the packed words and caps training at toy sizes.
These kernels train **on the packed words themselves**: the one-hot dot
product is a per-projection weight-table gather, so the forward pass is
the ``packed_lut`` select-tree machinery with the per-query tables
replaced by one shared weight table per output class, and the backward
pass is its transpose — gradient contributions scattered back into the
[k, 2^b] weight tables.

Four kernels:

``packed_linear_fwd_pallas``
    Margins: weight tables float [C, F*P] × corpus words uint32 [N, W]
    -> float32 [C, N], streaming corpus blocks; each b-bit field selects
    one of its 2^b table entries through a branchless select tree
    (``packed_lut.lut_scores``, on a transposed [rows, classes] tile)
    and selections accumulate in float32 in (word, field) order. The
    one-hot feature matrix never exists.

``packed_linear_fwd_masked_pallas``
    Same with a packed row-validity bitmask (``packing.pack_bitmask``
    layout): tombstoned rows emit margin 0.0 on device. The mask is
    data, not shape — churn never recompiles.

``packed_linear_bwd_pallas``
    Gradient scatter-accumulation: upstream margin gradients float32
    [C, N] × corpus words [N, W] -> table gradients float32 [C, F*P].
    Each corpus block expands to its one-hot tile *in register*
    (branchless field compares — never in HBM), 128 table columns at a
    time, and MXU matmuls ``g_tile @ onehot_columns`` accumulate every
    per-example contribution into the right (field, code) table column;
    blocks accumulate sequentially in a VMEM scratch accumulator.

``packed_linear_bwd_masked_pallas``
    Same with the validity bitmask: dead rows' one-hot rows are zeroed
    before the matmul, so tombstoned examples never touch the tables.

Bit-exactness: the jnp oracles (``ref.packed_linear_*_ref``) fix the
accumulation order — (word, field) for margins, ``block_n``-blocked
row chunks for gradients — and the kernels match them bit-for-bit.
Phantom table columns (field slots >= k from word padding, code values
>= n_codes from the power-of-two field width) are the *caller's*
responsibility: the kernels faithfully gather/scatter every field slot,
and ``repro.learn.features`` masks the phantom columns out of the
weight tables and gradients.

Padding: weight-table class rows pad with zeros (padded classes emit
garbage margins the wrapper slices off), corpus rows pad with zero
words *and* zero gradient columns, so padded rows contribute exact
zeros to every gradient sum.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.packed_collision import (_pad, live_rows, mask_rows,
                                            transposed_valid)
from repro.kernels.packed_lut import corpus_words, lut_scores

__all__ = ["packed_linear_fwd_pallas", "packed_linear_fwd_masked_pallas",
           "packed_linear_bwd_pallas", "packed_linear_bwd_masked_pallas",
           "onehot_tile"]


def _onehot_columns(words, bits: int, lo: int, width: int):
    """Columns [lo, lo + width) of the one-hot expansion of a packed
    tile: uint32 [bn, W] -> float32 [bn, width]. Column j holds 1.0 iff
    field slot j // P of the row holds code value j % P (P = 2**bits).
    Each column reads its word as a lane-broadcast of one tile column,
    so the tile is never reshaped."""
    p = 1 << bits
    per_word = (32 // bits) * p
    col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) + lo
    word_of = col // per_word
    shift = ((col % per_word) // p * bits).astype(jnp.uint32)
    code = (col % p).astype(jnp.uint32)
    shape = (words.shape[0], width)
    word = jnp.broadcast_to(words[:, lo // per_word:lo // per_word + 1],
                            shape)
    for w in range(lo // per_word + 1, (lo + width - 1) // per_word + 1):
        word = jnp.where(word_of == w,
                         jnp.broadcast_to(words[:, w:w + 1], shape), word)
    field = (word >> shift) & jnp.uint32(p - 1)
    return (field == code).astype(jnp.float32)


def onehot_tile(words, bits: int):
    """One-hot expand a packed tile in-register: uint32 [bn, W] ->
    float32 [bn, F*P] with F = W * 32/bits field slots and P = 2**bits
    entries per slot (the flat layout of ``rank.RankTables`` /
    ``learn.features``). Entry [n, f*P + c] is 1.0 iff field f of row n
    holds code value c — built from branchless field compares. The
    oracle's ``ref._onehot_rows`` is an independent construction of the
    same matrix (via ``packing.unpack_codes``); their equality — and
    hence kernel/oracle bit-exactness — is pinned by
    ``tests/test_learn.py``.
    """
    fp = words.shape[1] * (32 // bits) * (1 << bits)
    return _onehot_columns(words, bits, 0, fp)


# -- forward: margins ---------------------------------------------------------

def _fwd_kernel(*refs, bits: int, has_mask: bool):
    if has_mask:
        tab_ref, db_ref, valid_ref, o_ref = refs
    else:
        tab_ref, db_ref, o_ref = refs
    lut_scores(tab_ref, corpus_words(db_ref, o_ref.shape[1]), o_ref, bits)
    if has_mask:
        mask_rows(o_ref, live_rows(valid_ref), 0.0)


def _fwd_call(tables, words, valid_words, bits, block_c, block_n,
              interpret):
    cn, fp = tables.shape
    n, w = words.shape
    assert fp == w * (32 // bits) * (1 << bits), (tables.shape,
                                                  words.shape, bits)
    assert block_n % 32 == 0, block_n
    # classes ride the lanes of the transposed [rows, classes] tile: a
    # single block spans them all unless there are more than block_c
    bc = cn if cn <= block_c else block_c
    tT = _pad(tables.astype(jnp.float32), bc, 0).T         # [F*P, Cp]
    dbp = _pad(words, block_n, 0)
    cm, nm = tT.shape[1], dbp.shape[0]
    inputs = [tT, dbp]
    in_specs = [pl.BlockSpec((fp, bc), lambda i, j: (0, i)),
                pl.BlockSpec((block_n, w), lambda i, j: (j, 0))]
    if valid_words is not None:
        inputs.append(transposed_valid(valid_words, n, nm))
        in_specs.append(
            pl.BlockSpec((block_n // 32, 1), lambda i, j: (j, 0)))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, bits=bits,
                          has_mask=valid_words is not None),
        grid=(cm // bc, nm // block_n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_n, bc), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((nm, cm), jnp.float32),
        interpret=interpret,
    )(*inputs)
    return out[:n, :cn].T


@functools.partial(
    jax.jit,
    static_argnames=("bits", "block_c", "block_n", "interpret"))
def packed_linear_fwd_pallas(tables, words, bits: int, *,
                             block_c: int = 128, block_n: int = 512,
                             interpret: bool = False):
    """tables float [C, F*P] (class weight tables, flat ``RankTables``
    layout), words uint32 [N, W] -> margins float32 [C, N].

    margin[c, n] = sum over field slots f of tables[c, f*P + code(n, f)]
    accumulated in float32 in (word, field) order — bit-exact vs
    ``ref.packed_linear_fwd_ref``. Streams the corpus axis; the one-hot
    feature matrix never materializes.
    """
    return _fwd_call(tables, words, None, bits, block_c, block_n,
                     interpret)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "block_c", "block_n", "interpret"))
def packed_linear_fwd_masked_pallas(tables, words, valid_words, bits: int, *,
                                    block_c: int = 128, block_n: int = 512,
                                    interpret: bool = False):
    """``packed_linear_fwd_pallas`` over live rows only: ``valid_words``
    uint32 [ceil(N/32)] is the packed row-validity bitmask
    (``packing.pack_bitmask`` layout). Dead rows emit margin 0.0 —
    callers also exclude them from the loss, so the exact fill value is
    load-bearing only for bit-exactness vs
    ``ref.packed_linear_fwd_masked_ref``. The mask is data: tombstone
    churn never triggers a recompile.
    """
    return _fwd_call(tables, words, valid_words, bits, block_c, block_n,
                     interpret)


# -- backward: gradient scatter-accumulation into the weight tables -----------

def _bwd_kernel(*refs, bits: int, has_mask: bool):
    if has_mask:
        g_ref, db_ref, valid_ref, o_ref, acc_ref = refs
    else:
        g_ref, db_ref, o_ref, acc_ref = refs
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    db = db_ref[...]
    g = g_ref[...]
    live = live_rows(valid_ref) if has_mask else None
    fp = acc_ref.shape[1]
    width = math.gcd(fp, 128)
    for lo in range(0, fp, width):
        hot = _onehot_columns(db, bits, lo, width)        # [bn, width]
        if has_mask:
            # dead rows' one-hot rows -> 0: they add nothing to any sum
            hot = jnp.where(jnp.broadcast_to(live, hot.shape) != 0, hot,
                            0.0)
        acc_ref[:, lo:lo + width] += jnp.dot(
            g, hot, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = acc_ref[...]


def _bwd_call(g, words, valid_words, bits, block_c, block_n, interpret):
    cn, n = g.shape
    n2, w = words.shape
    assert n == n2, (g.shape, words.shape)
    assert block_n % 32 == 0, block_n
    fp = w * (32 // bits) * (1 << bits)
    gp = _pad(_pad(g.astype(jnp.float32), block_c, 0), block_n, 1)
    dbp = _pad(words, block_n, 0)
    cm, nm = gp.shape[0], dbp.shape[0]
    inputs = [gp, dbp]
    in_specs = [pl.BlockSpec((block_c, block_n), lambda i, j: (i, j)),
                pl.BlockSpec((block_n, w), lambda i, j: (j, 0))]
    if valid_words is not None:
        inputs.append(transposed_valid(valid_words, n, nm))
        in_specs.append(
            pl.BlockSpec((block_n // 32, 1), lambda i, j: (j, 0)))
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, bits=bits,
                          has_mask=valid_words is not None),
        grid=(cm // block_c, nm // block_n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_c, fp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((cm, fp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_c, fp), jnp.float32)],
        interpret=interpret,
    )(*inputs)
    return out[:cn]


@functools.partial(
    jax.jit,
    static_argnames=("bits", "block_c", "block_n", "interpret"))
def packed_linear_bwd_pallas(g, words, bits: int, *, block_c: int = 8,
                             block_n: int = 512, interpret: bool = False):
    """Backward pass of ``packed_linear_fwd_pallas``: upstream margin
    gradients g float32 [C, N] × words uint32 [N, W] -> weight-table
    gradients float32 [C, F*P].

    dTables[c, f*P + v] = sum over rows n with code(n, f) == v of
    g[c, n] — each block's contributions enter through in-register
    one-hot columns and MXU matmuls, accumulated block-sequentially in
    VMEM. Bit-exact vs ``ref.packed_linear_bwd_ref`` at the same
    ``block_n``. Padded rows carry zero gradient columns, so they
    contribute exact zeros.
    """
    return _bwd_call(g, words, None, bits, block_c, block_n, interpret)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "block_c", "block_n", "interpret"))
def packed_linear_bwd_masked_pallas(g, words, valid_words, bits: int, *,
                                    block_c: int = 8, block_n: int = 512,
                                    interpret: bool = False):
    """``packed_linear_bwd_pallas`` over live rows only: rows whose
    validity bit is clear contribute nothing to the scatter, so
    tombstoned examples never move a weight. Bit-exact vs
    ``ref.packed_linear_bwd_masked_ref`` at the same ``block_n``; the
    mask is data, not shape.
    """
    return _bwd_call(g, words, valid_words, bits, block_c, block_n,
                     interpret)
