"""Chunked ingest pipeline: raw corpus -> packed words -> store, streamed.

``IngestPipeline`` is the bulk-load driver above the encoder: it walks a
host-resident corpus (dense array or ``CsrMatrix``) in fixed-size row
chunks, encodes each chunk straight to packed words (fused kernels, no
f32/int32 corpus intermediates in HBM), and appends them to a store —
either the mutable ``index.SegmentLogStore`` (donated O(batch) tail
writes, via ``add_words``) or the immutable ``ann.CodeStore`` (merge per
chunk).  Chunks are padded up to a power-of-two row count so the whole
ingest compiles O(log chunk_rows) executables regardless of corpus size.

``encode_sharded`` is the data-parallel twin: corpus rows sharded over a
mesh axis, each shard streaming the SAME canonical R units locally (the
seed regenerates R everywhere — nothing is broadcast), so the packed
words are bit-identical to a single-device encode at any device count.
"""
from __future__ import annotations

import time
from types import MappingProxyType

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.encode.encoder import StreamingEncoder
from repro.encode.sparse import CsrMatrix
from repro.kernels import ops as _ops
from repro.obs import MetricsRegistry, default_flight_recorder, span
from repro.parallel.sharding import shard_map_unchecked

__all__ = ["IngestPipeline", "encode_sharded"]


class IngestPipeline:
    """Stream a corpus into a store in encoder-sized chunks.

    ``store`` may be a ``SegmentLogStore``-like object (has
    ``add_codes``/``add_words`` with external-id support; mutated in
    place) or a ``CodeStore``-like object (has ``merge``/``from_words``;
    rebound on ``self.store`` per chunk — read it back after
    ``ingest``).  ``stats`` is a read-only view of the ``repro.obs``
    counters accumulating rows, chunks and packed bytes across calls;
    each call opens an ``encode.ingest`` span and each chunk an
    ``encode.chunk`` span, on the profiler's clock.
    """

    def __init__(self, encoder: StreamingEncoder, store, *,
                 chunk_rows: int = 2048, impl: str = "auto",
                 registry: MetricsRegistry = None):
        if chunk_rows <= 0:
            raise ValueError(f"chunk_rows must be positive: {chunk_rows}")
        self.encoder = encoder
        self.store = store
        self.chunk_rows = int(chunk_rows)
        self.impl = impl
        self.registry = registry if registry is not None \
            else MetricsRegistry(enabled=True)
        self._c_rows = self.registry.counter("encode.rows")
        self._c_chunks = self.registry.counter("encode.chunks")
        self._c_bytes = self.registry.counter("encode.packed_bytes")

    @property
    def stats(self):
        """Read-only compat view of the ingest counters."""
        return MappingProxyType({"rows": self._c_rows.value,
                                 "chunks": self._c_chunks.value,
                                 "packed_bytes": self._c_bytes.value})

    def _encode_chunk(self, x, lo: int, hi: int):
        """Rows [lo, hi) -> packed words [hi-lo, W]; the chunk is padded
        up to a power of two (zero rows, dropped after the kernel) so
        ragged tails never compile a fresh executable."""
        m = hi - lo
        mp = min(1 << (m - 1).bit_length(), self.chunk_rows)
        if isinstance(x, CsrMatrix):
            chunk = x.row_slice(lo, hi)
            if mp > m:
                pad = np.zeros(mp - m, np.int64)
                chunk = CsrMatrix(
                    indptr=np.concatenate([chunk.indptr,
                                           pad + chunk.indptr[-1]]),
                    indices=chunk.indices, data=chunk.data,
                    shape=(mp, chunk.d))
        elif isinstance(x, jax.Array):
            chunk = x[lo:hi]
            if mp > m:
                chunk = jnp.pad(chunk, ((0, mp - m), (0, 0)))
        else:
            # host corpora stay host-side: the encoder ships unit slabs
            # to the device itself (O(chunk·unit), not O(chunk·D))
            chunk = np.asarray(x[lo:hi], np.float32)
            if mp > m:
                chunk = np.pad(chunk, ((0, mp - m), (0, 0)))
        words = self.encoder.encode_packed(chunk, impl=self.impl)
        return words[:m]

    def ingest(self, x, ids=None) -> np.ndarray:
        """Encode + append every row of ``x`` (dense [n, D] or
        ``CsrMatrix``); returns the external ids (int64 [n]; for
        ``CodeStore`` targets, the appended row positions)."""
        n = x.n if isinstance(x, CsrMatrix) else int(np.asarray(
            x.shape[0]))
        if ids is not None:
            if not hasattr(self.store, "add_codes"):
                raise ValueError(
                    "explicit ids need an id-aware store (SegmentLogStore); "
                    "CodeStore rows are addressed by position only")
            ids = np.asarray(ids, np.int64)
            if ids.shape != (n,):
                raise ValueError(f"ids {ids.shape} != ({n},)")
            # validate the WHOLE batch before the first chunk is
            # appended: a clash surfacing mid-loop would leave earlier
            # chunks permanently ingested (no rollback)
            if np.unique(ids).size != n:
                raise ValueError("duplicate ids within one ingest")
            clash = [int(i) for i in ids if i in self.store]
            if clash:
                raise ValueError(f"ids already live (upsert instead): "
                                 f"{clash[:5]}")
        out_ids = []
        t_ing = time.perf_counter()
        with span("encode.ingest", rows=n) as sp:
            for lo in range(0, n, self.chunk_rows):
                hi = min(lo + self.chunk_rows, n)
                with span("encode.chunk", rows=hi - lo) as csp:
                    words = csp.sync(self._encode_chunk(x, lo, hi))
                chunk_ids = None if ids is None else ids[lo:hi]
                if hasattr(self.store, "add_codes"):        # mutable log
                    out_ids.append(np.asarray(
                        self.store.add_words(words, ids=chunk_ids)))
                else:                                       # immutable store
                    start = self.store.n
                    self.store = self.store.add_words(words)
                    out_ids.append(np.arange(start, start + (hi - lo),
                                             dtype=np.int64))
                self._c_rows.inc(hi - lo)
                self._c_chunks.inc()
                self._c_bytes.inc(int(words.size) * 4)
            sp.set(chunks=self._c_chunks.value)
        # chunk encodes round-trip to host (np words), so t_end here is
        # effectively device-synced
        default_flight_recorder().record(
            "encode.ingest", t_ing, time.perf_counter(), batch=n,
            generation=getattr(self.store, "generation", -1), synced=True)
        return (np.concatenate(out_ids) if out_ids
                else np.zeros(0, np.int64))


def encode_sharded(encoder: StreamingEncoder, x, mesh: Mesh,
                   axis: str = "data", impl: str = "auto"):
    """Data-parallel fused encode: dense x [n, D] row-sharded over
    ``mesh[axis]`` -> packed uint32 [n, W] (n must divide the axis;
    CSR corpora shard at the pipeline level instead — run one
    ``IngestPipeline`` per host over its row slice).

    Every shard regenerates the same canonical R units from the seed —
    no weight broadcast, no gather — runs the sketcher's scan
    projection over its local rows and the fused code+pack epilogue
    kernel (``kernels.encode_fused``, dispatched per ``impl``), so the
    result matches the unsharded streaming encode bit-for-bit at ANY
    device count (the reproducibility contract of ``core.sketch``)."""
    s = encoder.sketcher
    x = jnp.asarray(x)
    if x.shape[0] % mesh.shape[axis]:
        raise ValueError(f"n={x.shape[0]} not divisible by mesh axis "
                         f"{axis} ({mesh.shape[axis]})")

    def local(xs):
        # the sketcher's canonical scan-projection: every shard streams
        # the same units in the same order as the single-device oracle
        return _ops.code_pack(s.project(xs), s.spec, s._offsets,
                              impl=impl)

    fn = shard_map_unchecked(local, mesh, in_specs=(P(axis, None),),
                             out_specs=P(axis, None))
    return jax.jit(fn)(x)
