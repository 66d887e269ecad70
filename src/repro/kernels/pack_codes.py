"""Bit-packing Pallas kernel: b-bit codes -> uint32 words.

Row-blocked: each grid step packs (bm, K) int32 codes into (bm, K·b/32)
uint32 words entirely in VMEM (``pack_fields``, shared with the fused
encode epilogue). K is padded to a lane multiple by the wrapper (zero
codes land in high bits or in whole padding words, which are sliced off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.packing import codes_per_word

__all__ = ["pack_codes_pallas"]


def pack_fields(codes, bits: int):
    """In-register pack: int32 codes [bm, kp] (each < 2^bits, kp a
    multiple of 32/bits) -> uint32 words [bm, kp·bits/32].

    The tile is transposed so the 32/b fields of a word sit on
    consecutive sublanes; the fields are disjoint, so their int32 sum
    is their bitwise-or (bit 31 wraps, exactly), and the result is
    bitcast to uint32 — the words of ``core.packing.pack_codes``.
    """
    cpw = codes_per_word(bits)
    bm, kp = codes.shape
    ct = codes.T.reshape(kp // cpw, cpw, bm)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, cpw, 1), 1) * bits
    words = jnp.sum(ct << shifts, axis=1)
    return jax.lax.bitcast_convert_type(words.T, jnp.uint32)


def _kernel(c_ref, o_ref, *, bits: int):
    o_ref[...] = pack_fields(c_ref[...].astype(jnp.int32), bits)


@functools.partial(jax.jit, static_argnames=("bits", "block_m", "interpret"))
def pack_codes_pallas(codes, bits: int, *, block_m: int = 256,
                      interpret: bool = False):
    """codes int32 [M, K] -> uint32 [M, ceil(K/(32/bits))]."""
    cpw = codes_per_word(bits)
    m, k = codes.shape
    kpad = (-k) % 128             # a lane multiple (32/b divides 128)
    if kpad:
        codes = jnp.pad(codes, ((0, 0), (0, kpad)))
    mpad = (-m) % block_m
    if mpad:
        codes = jnp.pad(codes, ((0, mpad), (0, 0)))
    mp, kp = codes.shape
    nw = kp // cpw
    out = pl.pallas_call(
        functools.partial(_kernel, bits=bits),
        grid=(mp // block_m,),
        in_specs=[pl.BlockSpec((block_m, kp), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_m, nw), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, nw), jnp.uint32),
        interpret=interpret,
    )(codes)
    return out[:m, :(k + cpw - 1) // cpw]
