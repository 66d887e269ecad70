"""Fused projection → coding → bit-packing Pallas kernels (the ingest path).

The paper's storage economy only pays off end-to-end if *producing* the
codes is as lean as storing them.  ``kernels/proj_code.py`` fuses the
GEMM with the coding scheme but still writes int32 codes (4 bytes per
projection) to HBM before a separate packing pass; these kernels take
the epilogue one stage further, so the ONLY HBM write-back of an encode
is the final packed uint32 words — b bits per projection, a 16x traffic
cut at b=2 versus f32 projections and 16x versus int32 codes.

Two entry points share the epilogue:

``encode_fused_pallas``   x [M, D] @ r [D, K] → uint32 words [M, W]:
    grid (M/bm, D/bd), f32 VMEM accumulator over the reduction axis
    (minor-most = sequential on TPU), code + pack applied in-register on
    the final reduction step.  K is held whole per tile (acc [bm, K]
    f32 ≈ 128 KB at K=256) because packing mixes all K fields of a row.
``code_pack_pallas``      z [M, K] → uint32 words [M, W]:
    the epilogue alone, for pre-projected values — the finalize stage of
    the matrix-free streaming path (``repro.encode.encoder``), whose
    GEMM accumulates across host-loop steps with a donated slab.

Both are bit-exact (packed words included) against the jnp oracles
``kernels.ref.encode_fused_ref`` / ``code_pack_ref`` for all four
schemes; padded K fields are forced to code 0 in-register, matching the
zero-padding of ``core.packing.pack_codes``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import codes_per_word
from repro.core.schemes import CodeSpec
from repro.kernels.pack_codes import pack_fields
from repro.kernels.proj_code import _apply_code, _pad_to

__all__ = ["encode_fused_pallas", "code_pack_pallas"]


def _code_and_pack(z, q_row, spec: CodeSpec, k: int):
    """In-register epilogue: f32 tile z [bm, kp] -> uint32 [bm, kp*b/32].

    Fields past the real ``k`` are forced to code 0 (the pack oracle's
    zero padding) before ``pack_codes.pack_fields`` packs the tile.
    """
    codes = _apply_code(z, q_row, spec)
    col = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    return pack_fields(jnp.where(col < k, codes, 0), spec.bits)


def _fused_kernel(x_ref, r_ref, q_ref, o_ref, acc_ref, *,
                  spec: CodeSpec, k: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], r_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = _code_and_pack(acc_ref[...], q_ref[...], spec, k)


@functools.partial(
    jax.jit,
    static_argnames=("spec", "block_m", "block_d", "interpret"))
def encode_fused_pallas(x, r, spec: CodeSpec, q: Optional[jax.Array] = None,
                        *, block_m: int = 128, block_d: int = 512,
                        interpret: bool = False):
    """x [M, D] (f32/bf16) @ r [D, K] -> packed uint32 [M, ceil(K·b/32)].

    Fuses GEMM-accumulate, the coding scheme under ``spec`` and b-bit
    packing; neither f32 projections nor int32 codes ever reach HBM.
    ``q`` (offset scheme) is a [K] vector; ignored (zeros) otherwise.
    """
    m, d = x.shape
    d2, k = r.shape
    assert d == d2, (x.shape, r.shape)
    if q is None:
        q = jnp.zeros((k,), jnp.float32)
    cpw = codes_per_word(spec.bits)
    lane = 128 if 128 % cpw == 0 else cpw      # cpw divides 128 for b<=16
    xp = _pad_to(_pad_to(x, block_m, 0), block_d, 1)
    rp = _pad_to(_pad_to(r, lane, 1), block_d, 0)
    qp = _pad_to(q.astype(jnp.float32)[None, :], lane, 1)
    mp, dp = xp.shape
    kp = rp.shape[1]
    nw = kp // cpw
    grid = (mp // block_m, dp // block_d)

    out = pl.pallas_call(
        functools.partial(_fused_kernel, spec=spec, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_d), lambda i, s: (i, s)),
            pl.BlockSpec((block_d, kp), lambda i, s: (s, 0)),
            pl.BlockSpec((1, kp), lambda i, s: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, nw), lambda i, s: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, nw), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((block_m, kp), jnp.float32)],
        interpret=interpret,
    )(xp, rp, qp)
    # lane padding beyond the real packed width holds all-zero fields
    return out[:m, :(k + cpw - 1) // cpw]


def _pack_kernel(z_ref, q_ref, o_ref, *, spec: CodeSpec, k: int):
    o_ref[...] = _code_and_pack(z_ref[...].astype(jnp.float32),
                                q_ref[...], spec, k)


@functools.partial(
    jax.jit, static_argnames=("spec", "block_m", "interpret"))
def code_pack_pallas(z, spec: CodeSpec, q: Optional[jax.Array] = None,
                     *, block_m: int = 256, interpret: bool = False):
    """Projected z [M, K] float -> packed uint32 [M, ceil(K·b/32)].

    The fused epilogue alone: coding scheme + b-bit pack in one VMEM
    pass (row-blocked), int32 codes never materialized.
    """
    m, k = z.shape
    if q is None:
        q = jnp.zeros((k,), jnp.float32)
    cpw = codes_per_word(spec.bits)
    lane = 128 if 128 % cpw == 0 else cpw
    zp = _pad_to(_pad_to(z, block_m, 0), lane, 1)
    qp = _pad_to(q.astype(jnp.float32)[None, :], lane, 1)
    mp, kp = zp.shape
    nw = kp // cpw
    out = pl.pallas_call(
        functools.partial(_pack_kernel, spec=spec, k=k),
        grid=(mp // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, kp), lambda i: (i, 0)),
            pl.BlockSpec((1, kp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, nw), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, nw), jnp.uint32),
        interpret=interpret,
    )(zp, qp)
    return out[:m, :(k + cpw - 1) // cpw]
