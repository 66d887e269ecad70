"""Public jit'd wrappers for the Pallas kernels.

Dispatch policy: on TPU run the compiled kernels — never the oracle or
interpret mode unless the caller asks for it; elsewhere either run the
kernels in interpret mode (exact semantics, used by tests) or fall back to
the jnp oracle (fast CPU path, used by benchmarks/examples). ``impl``:
  'auto'    -> 'pallas' on TPU, 'ref' otherwise
  'pallas'  -> kernel (interpret=True off-TPU)
  'ref'     -> jnp oracle

Every dispatch also reports its family + static shape dims to
``repro.obs.kernelstats`` (invocation counts, modeled FLOPs/HBM bytes —
the live roofline). Calls made inside a jit trace are flagged ``traced``:
they dispatch once per compile, not per execution.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.core.packing import packed_width as _packed_width
from repro.core.schemes import CodeSpec
from repro.kernels import autotune as _autotune
from repro.kernels import ref as _ref
from repro.obs import kernelstats as _kstats
from repro.kernels.collision import collision_counts_pallas
from repro.kernels.pack_codes import pack_codes_pallas
from repro.kernels.packed_collision import (
    packed_collision_counts_pallas, packed_topk_masked_pallas,
    packed_topk_pallas)
from repro.kernels.packed_linear import (
    packed_linear_bwd_masked_pallas, packed_linear_bwd_pallas,
    packed_linear_fwd_masked_pallas, packed_linear_fwd_pallas)
from repro.kernels.packed_lut import (
    packed_lut_rerank_pallas, packed_lut_topk_masked_pallas,
    packed_lut_topk_pallas)
from repro.kernels.encode_fused import code_pack_pallas, encode_fused_pallas
from repro.kernels.fused_scored import (fused_scored_topk_masked_pallas,
                                        fused_scored_topk_pallas)
from repro.kernels.proj_code import coded_project_pallas

__all__ = ["coded_project", "encode_fused", "code_pack", "pack_codes",
           "collision_counts",
           "packed_collision_counts", "packed_topk", "packed_topk_masked",
           "packed_lut_topk", "packed_lut_topk_masked", "packed_lut_rerank",
           "fused_scored_topk", "fused_scored_topk_masked",
           "packed_linear_fwd", "packed_linear_fwd_masked",
           "packed_linear_bwd", "packed_linear_bwd_masked"]


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return impl


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _rec(family: str, *arrays, **dims):
    """Report one dispatch to the kernel flight recorder (repro.obs)."""
    _kstats.record(family,
                   traced=any(isinstance(a, jax.core.Tracer)
                              for a in arrays), **dims)


def _tuned(op: str, dtype, block_kwargs: dict, **dims) -> dict:
    """Block kwargs for a pallas dispatch: explicit caller kwargs win;
    otherwise consult the autotune cache (``kernels.autotune.lookup``,
    a pure host-dict read) — cold caches return {} and the kernel
    defaults apply. Tuned knobs are numerics-safe by construction, so
    this indirection can only change timing."""
    if block_kwargs:
        return block_kwargs
    return _autotune.lookup(op, dtype, **dims)


def coded_project(x, r, spec: CodeSpec, q: Optional[jax.Array] = None,
                  impl: str = "auto", **block_kwargs):
    """Fused encode(x @ r): [M, D] x [D, K] -> int32 codes [M, K]."""
    _rec("coded_project", x, r, m=x.shape[0], d=x.shape[1], k=r.shape[1])
    if _resolve(impl) == "ref":
        return _ref.coded_project_ref(x, r, spec, q)
    kw = _tuned("coded_project", x.dtype, block_kwargs,
                m=x.shape[0], d=x.shape[1], k=r.shape[1])
    return coded_project_pallas(x, r, spec, q, interpret=_interpret(), **kw)


def encode_fused(x, r, spec: CodeSpec, q: Optional[jax.Array] = None,
                 impl: str = "auto", **block_kwargs):
    """Fused pack(encode(x @ r)): [M, D] x [D, K] -> packed uint32
    [M, ceil(K·b/32)] — the one-kernel ingest path (projections and
    int32 codes never reach HBM)."""
    _rec("encode_fused", x, r, m=x.shape[0], d=x.shape[1], k=r.shape[1],
         w=_packed_width(r.shape[1], spec.bits))
    if _resolve(impl) == "ref":
        return _ref.encode_fused_ref(x, r, spec, q)
    kw = _tuned("encode_fused", x.dtype, block_kwargs,
                m=x.shape[0], d=x.shape[1], k=r.shape[1])
    return encode_fused_pallas(x, r, spec, q, interpret=_interpret(), **kw)


def code_pack(z, spec: CodeSpec, q: Optional[jax.Array] = None,
              impl: str = "auto", **block_kwargs):
    """Fused pack(encode(z)) of pre-projected values: [M, K] float ->
    packed uint32 [M, ceil(K·b/32)] (the streaming encode finalize)."""
    _rec("code_pack", z, m=z.shape[0], k=z.shape[1],
         w=_packed_width(z.shape[1], spec.bits))
    if _resolve(impl) == "ref":
        return _ref.code_pack_ref(z, spec, q)
    kw = _tuned("code_pack", z.dtype, block_kwargs,
                m=z.shape[0], k=z.shape[1])
    return code_pack_pallas(z, spec, q, interpret=_interpret(), **kw)


def pack_codes(codes, bits: int, impl: str = "auto", **block_kwargs):
    """Pack b-bit codes into uint32 words: [M, K] -> [M, K*b/32]."""
    _rec("pack_codes", codes, m=codes.shape[0], k=codes.shape[1],
         w=_packed_width(codes.shape[1], bits))
    if _resolve(impl) == "ref":
        return _ref.pack_codes_ref(codes, bits)
    kw = _tuned("pack_codes", codes.dtype, block_kwargs,
                m=codes.shape[0], k=codes.shape[1])
    return pack_codes_pallas(codes, bits, interpret=_interpret(), **kw)


def collision_counts(codes_q, codes_db, impl: str = "auto", **block_kwargs):
    """All-pairs collision counts: [Q, K], [N, K] -> int32 [Q, N]."""
    _rec("collision_counts", codes_q, codes_db, q=codes_q.shape[0],
         n=codes_db.shape[0], k=codes_q.shape[1])
    if _resolve(impl) == "ref":
        return _ref.collision_counts_ref(codes_q, codes_db)
    kw = _tuned("collision_counts", codes_q.dtype, block_kwargs,
                q=codes_q.shape[0], n=codes_db.shape[0])
    return collision_counts_pallas(codes_q, codes_db, interpret=_interpret(),
                                   **kw)


def packed_collision_counts(words_q, words_db, bits: int, k: int,
                            impl: str = "auto", **block_kwargs):
    """All-pairs counts on packed words: [Q, W], [N, W] -> int32 [Q, N]."""
    _rec("packed_collision_counts", words_q, words_db,
         q=words_q.shape[0], n=words_db.shape[0], w=words_q.shape[1])
    if _resolve(impl) == "ref":
        return _ref.packed_collision_ref(words_q, words_db, bits, k)
    kw = _tuned("packed_collision_counts", words_q.dtype, block_kwargs,
                q=words_q.shape[0], n=words_db.shape[0], w=words_q.shape[1])
    return packed_collision_counts_pallas(words_q, words_db, bits, k,
                                          interpret=_interpret(), **kw)


def packed_topk(words_q, words_db, bits: int, k: int, top_k: int,
                impl: str = "auto", **block_kwargs):
    """Streaming top-k search on packed words -> (counts, ids) [Q, top_k]."""
    _rec("packed_topk", words_q, words_db, q=words_q.shape[0],
         n=words_db.shape[0], w=words_q.shape[1], top_k=top_k)
    if _resolve(impl) == "ref":
        return _ref.packed_topk_ref(words_q, words_db, bits, k, top_k)
    kw = _tuned("packed_topk", words_q.dtype, block_kwargs,
                q=words_q.shape[0], n=words_db.shape[0],
                w=words_q.shape[1], top_k=top_k)
    return packed_topk_pallas(words_q, words_db, bits, k, top_k,
                              interpret=_interpret(), **kw)


def packed_topk_masked(words_q, words_db, valid_words, bits: int, k: int,
                       top_k: int, impl: str = "auto", **block_kwargs):
    """Streaming top-k over live rows only (packed validity bitmask)."""
    _rec("packed_topk_masked", words_q, words_db, q=words_q.shape[0],
         n=words_db.shape[0], w=words_q.shape[1], top_k=top_k)
    if _resolve(impl) == "ref":
        return _ref.packed_topk_masked_ref(words_q, words_db, valid_words,
                                           bits, k, top_k)
    kw = _tuned("packed_topk_masked", words_q.dtype, block_kwargs,
                q=words_q.shape[0], n=words_db.shape[0],
                w=words_q.shape[1], top_k=top_k)
    return packed_topk_masked_pallas(words_q, words_db, valid_words, bits, k,
                                     top_k, interpret=_interpret(), **kw)


def packed_lut_topk(q_tables, words_db, bits: int, top_k: int,
                    impl: str = "auto", **block_kwargs):
    """LUT-scored streaming top-k: [Q, F*P] float tables x [N, W] packed
    words -> (scores f32, ids int32) [Q, top_k]."""
    _rec("packed_lut_topk", q_tables, words_db, q=q_tables.shape[0],
         n=words_db.shape[0], w=words_db.shape[1], t=q_tables.shape[1],
         k=q_tables.shape[1] >> bits, top_k=top_k)
    if _resolve(impl) == "ref":
        return _ref.packed_lut_topk_ref(q_tables, words_db, bits, top_k)
    kw = _tuned("packed_lut_topk", q_tables.dtype, block_kwargs,
                q=q_tables.shape[0], n=words_db.shape[0],
                w=words_db.shape[1], t=q_tables.shape[1], top_k=top_k)
    return packed_lut_topk_pallas(q_tables, words_db, bits, top_k,
                                  interpret=_interpret(), **kw)


def packed_lut_topk_masked(q_tables, words_db, valid_words, bits: int,
                           top_k: int, impl: str = "auto", **block_kwargs):
    """LUT-scored streaming top-k over live rows only (packed bitmask)."""
    _rec("packed_lut_topk_masked", q_tables, words_db,
         q=q_tables.shape[0], n=words_db.shape[0], w=words_db.shape[1],
         t=q_tables.shape[1], k=q_tables.shape[1] >> bits, top_k=top_k)
    if _resolve(impl) == "ref":
        return _ref.packed_lut_topk_masked_ref(q_tables, words_db,
                                               valid_words, bits, top_k)
    kw = _tuned("packed_lut_topk_masked", q_tables.dtype, block_kwargs,
                q=q_tables.shape[0], n=words_db.shape[0],
                w=words_db.shape[1], t=q_tables.shape[1], top_k=top_k)
    return packed_lut_topk_masked_pallas(q_tables, words_db, valid_words,
                                         bits, top_k,
                                         interpret=_interpret(), **kw)


def packed_linear_fwd(tables, words, bits: int, impl: str = "auto",
                      **block_kwargs):
    """Packed-linear margins: class weight tables [C, F*P] float x
    packed words [N, W] -> float32 [C, N] (repro.learn forward)."""
    _rec("packed_linear_fwd", tables, words, c=tables.shape[0],
         n=words.shape[0], w=words.shape[1], t=tables.shape[1],
         k=tables.shape[1] >> bits)
    if _resolve(impl) == "ref":
        return _ref.packed_linear_fwd_ref(tables, words, bits)
    kw = _tuned("packed_linear_fwd", tables.dtype, block_kwargs,
                c=tables.shape[0], n=words.shape[0], t=tables.shape[1])
    return packed_linear_fwd_pallas(tables, words, bits,
                                    interpret=_interpret(), **kw)


def packed_linear_fwd_masked(tables, words, valid_words, bits: int,
                             impl: str = "auto", **block_kwargs):
    """Packed-linear margins over live rows only (packed bitmask);
    tombstoned rows emit margin 0.0."""
    _rec("packed_linear_fwd_masked", tables, words, c=tables.shape[0],
         n=words.shape[0], w=words.shape[1], t=tables.shape[1],
         k=tables.shape[1] >> bits)
    if _resolve(impl) == "ref":
        return _ref.packed_linear_fwd_masked_ref(tables, words, valid_words,
                                                 bits)
    kw = _tuned("packed_linear_fwd_masked", tables.dtype, block_kwargs,
                c=tables.shape[0], n=words.shape[0], t=tables.shape[1])
    return packed_linear_fwd_masked_pallas(tables, words, valid_words, bits,
                                           interpret=_interpret(), **kw)


def packed_linear_bwd(g, words, bits: int, impl: str = "auto",
                      **block_kwargs):
    """Weight-table gradients: margin gradients [C, N] float32 x packed
    words [N, W] -> float32 [C, F*P] (repro.learn backward)."""
    _rec("packed_linear_bwd", g, words, c=g.shape[0], n=words.shape[0],
         w=words.shape[1], t=(words.shape[1] * (32 // bits)) << bits,
         k=words.shape[1] * (32 // bits))
    if _resolve(impl) == "ref":
        return _ref.packed_linear_bwd_ref(g, words, bits, **block_kwargs)
    kw = _tuned("packed_linear_bwd", g.dtype, block_kwargs,
                c=g.shape[0], n=words.shape[0], w=words.shape[1])
    return packed_linear_bwd_pallas(g, words, bits, interpret=_interpret(),
                                    **kw)


def packed_linear_bwd_masked(g, words, valid_words, bits: int,
                             impl: str = "auto", **block_kwargs):
    """Weight-table gradients over live rows only: tombstoned rows'
    contributions are zeroed on device before the scatter."""
    _rec("packed_linear_bwd_masked", g, words, c=g.shape[0],
         n=words.shape[0], w=words.shape[1],
         t=(words.shape[1] * (32 // bits)) << bits,
         k=words.shape[1] * (32 // bits))
    if _resolve(impl) == "ref":
        return _ref.packed_linear_bwd_masked_ref(g, words, valid_words,
                                                 bits, **block_kwargs)
    kw = _tuned("packed_linear_bwd_masked", g.dtype, block_kwargs,
                c=g.shape[0], n=words.shape[0], w=words.shape[1])
    return packed_linear_bwd_masked_pallas(g, words, valid_words, bits,
                                           interpret=_interpret(), **kw)


def packed_lut_rerank(q_tables, cand_words, cand_valid, bits: int,
                      top_k: int, impl: str = "auto", **block_kwargs):
    """Re-rank gathered candidates [Q, M, W] by per-query LUT scores ->
    (scores f32, candidate positions int32) [Q, top_k]."""
    _rec("packed_lut_rerank", q_tables, cand_words,
         q=q_tables.shape[0], c=cand_words.shape[1],
         w=cand_words.shape[2], t=q_tables.shape[1],
         k=q_tables.shape[1] >> bits, top_k=top_k)
    if _resolve(impl) == "ref":
        return _ref.packed_lut_rerank_ref(q_tables, cand_words, cand_valid,
                                          bits, top_k)
    kw = _tuned("packed_lut_rerank", q_tables.dtype, block_kwargs,
                q=q_tables.shape[0], m=cand_words.shape[1],
                t=q_tables.shape[1], top_k=top_k)
    return packed_lut_rerank_pallas(q_tables, cand_words, cand_valid, bits,
                                    top_k, interpret=_interpret(), **kw)


def fused_scored_topk(q_words, q_tables, words_db, bits: int, k: int,
                      rerank_m: int, top_k: int, scales=None,
                      impl: str = "auto", **block_kwargs):
    """Single-pass scored search: exact stable coarse top-``rerank_m``
    by collision count, re-ranked by per-query LUT score, in one
    streamed kernel -> (scores f32, corpus ids int32) [Q, top_k].
    ``scales`` float32 [Q, W] (powers of two) selects the int8-table
    path."""
    _rec("fused_scored_topk", q_words, q_tables, words_db,
         q=q_words.shape[0], n=words_db.shape[0], w=q_words.shape[1],
         t=q_tables.shape[1], k=q_tables.shape[1] >> bits, top_k=top_k)
    if _resolve(impl) == "ref":
        return _ref.fused_scored_topk_ref(q_words, q_tables, words_db,
                                          bits, k, rerank_m, top_k,
                                          scales=scales)
    kw = _tuned("fused_scored_topk", q_tables.dtype, block_kwargs,
                q=q_words.shape[0], n=words_db.shape[0],
                w=q_words.shape[1], t=q_tables.shape[1], top_k=top_k)
    return fused_scored_topk_pallas(q_words, q_tables, words_db, bits, k,
                                    rerank_m, top_k, scales=scales,
                                    interpret=_interpret(), **kw)


def fused_scored_topk_masked(q_words, q_tables, words_db, valid_words,
                             bits: int, k: int, rerank_m: int, top_k: int,
                             scales=None, impl: str = "auto",
                             **block_kwargs):
    """``fused_scored_topk`` over live rows only (packed row-validity
    bitmask) — the mutable-index segment path; all-dead segments return
    pure (-inf, -1) sentinels."""
    _rec("fused_scored_topk_masked", q_words, q_tables, words_db,
         q=q_words.shape[0], n=words_db.shape[0], w=q_words.shape[1],
         t=q_tables.shape[1], k=q_tables.shape[1] >> bits, top_k=top_k)
    if _resolve(impl) == "ref":
        return _ref.fused_scored_topk_masked_ref(
            q_words, q_tables, words_db, valid_words, bits, k, rerank_m,
            top_k, scales=scales)
    kw = _tuned("fused_scored_topk_masked", q_tables.dtype, block_kwargs,
                q=q_words.shape[0], n=words_db.shape[0],
                w=q_words.shape[1], t=q_tables.shape[1], top_k=top_k)
    return fused_scored_topk_masked_pallas(
        q_words, q_tables, words_db, valid_words, bits, k, rerank_m,
        top_k, scales=scales, interpret=_interpret(), **kw)
