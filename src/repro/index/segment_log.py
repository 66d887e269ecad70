"""Segment-log store: the mutable ingestion path over immutable packed codes.

The PR-1 ``CodeStore`` is append-by-copy: every ``add`` concatenates the
whole corpus (O(N) HBM traffic) and changes the corpus shape, invalidating
every jit cache entry. The ``SegmentLogStore`` turns ingestion into a log:

* **Tail buffer** — a preallocated device-resident uint32 buffer of
  ``tail_rows`` rows. ``add_codes`` packs the batch and writes it with a
  *donated* ``dynamic_update_slice``, so the update is in-place: O(batch)
  bytes copied, O(corpus) never touched, and the buffer shape never
  changes so the write executable compiles once per chunk size.
* **Sealed segments** — when the tail fills it is sealed as-is (the buffer
  simply stops being written) and a fresh tail is allocated. Sealed
  segments are content-immutable; every search jit entry keyed on a
  segment shape stays valid forever.
* **Tombstones** — deletes flip one bit in a packed per-segment validity
  bitmask (host-authoritative ``np.uint32``, device copy cached until the
  next delete). Dead rows are skipped *on device* by the masked streaming
  top-k kernel (``kernels.packed_collision.packed_topk_masked_pallas``);
  the mask is data, not shape, so tombstones cost zero recompiles.
* **Upserts** — an id→(segment, row) map lets ``upsert_codes`` tombstone
  the id's current row and append the new version; external ids are
  stable across upserts, seals and compactions.

Row identity: every row carries an external id (monotonic ``next_id`` by
default). The store's *iteration order* — sealed segments in log order,
live rows in row order, then the tail — defines search tie-breaking, and
is exactly the row order of a fresh ``CodeStore`` built from
``live_codes()``: the bit-exactness contract the tests enforce.

Placement: a store given ``devices`` keeps segment ``s`` of the log
(sealed or tail) whole on ``devices[s % len(devices)]``: its words and
hashes, the device copies of its validity and ids, and the chunks the
tail is written with. Compaction and restore keep the rule by moving any
segment whose log position changed; searches run each segment on its
own chip and merge on ``devices[0]`` (``repro.index.engine``). Without
``devices`` every array stays where JAX puts it by default.

Lifecycle ops live beside this module: ``compaction`` (size-tiered merge
that drops tombstones), ``snapshot`` (durability via ``repro.checkpoint``).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.ann.bands import BandSpec, band_hashes
from repro.core import packing as _packing
from repro.kernels import ops as _ops
from repro.obs import MetricsRegistry, span

__all__ = ["Segment", "SegmentLogStore"]


def _np_pack_bitmask(flags: np.ndarray) -> np.ndarray:
    """Host-side ``packing.pack_bitmask``: bool [n] -> uint32 [ceil(n/32)]."""
    packed = np.packbits(flags.astype(bool), bitorder="little")
    pad = (-packed.size) % 4
    if pad:
        packed = np.pad(packed, (0, pad))
    return packed.view(np.uint32)


def _np_unpack_bitmask(words: np.ndarray, n: int) -> np.ndarray:
    """Inverse of ``_np_pack_bitmask``: uint32 words -> bool [n]."""
    return np.unpackbits(words.view(np.uint8), bitorder="little")[:n] \
        .astype(bool)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_rows(buf, rows, start):
    """In-place (donated) row-slab write into a preallocated buffer."""
    return jax.lax.dynamic_update_slice(buf, rows, (start, 0))


class Segment:
    """One log segment: content-immutable device rows + mutable liveness.

    ``words``/``hashes`` are device arrays that never change shape; for
    the tail, rows past ``length`` are unwritten (their validity bits are
    0, so search can treat the full buffer as the segment). ``valid`` is
    the host-authoritative packed bitmask; ``valid_dev``/``ids_dev`` are
    demand-built device copies, dropped on mutation. ``device`` is the
    chip a placed store keeps all of them on (None: unplaced).
    """

    __slots__ = ("words", "hashes", "ids", "valid", "live", "length",
                 "device", "_valid_dev", "_ids_dev")

    def __init__(self, words, hashes, ids, valid, live, length,
                 device=None):
        self.words = words            # uint32 [cap, W] device
        self.hashes = hashes          # uint32 [cap, L] device | None
        self.ids = ids                # int64 [cap] host
        self.valid = valid            # uint32 [ceil(cap/32)] host bitmask
        self.live = live              # live-row count
        self.length = length          # written rows (== cap once sealed)
        self.device = device          # jax.Device | None (unplaced)
        self._valid_dev = None
        self._ids_dev = None

    @property
    def cap(self) -> int:
        """Row capacity of the segment's device buffer."""
        return self.words.shape[0]

    def valid_dev(self):
        """Device copy of the packed validity bitmask, uint32
        [ceil(cap/32)] (cached until the next mutation)."""
        if self._valid_dev is None:
            self._valid_dev = jnp.asarray(self.valid, device=self.device)
        return self._valid_dev

    def ids_dev(self):
        """Device copy of the external ids, int32 [cap] (-1 =
        unwritten slot; cached until the next mutation)."""
        if self._ids_dev is None:
            self._ids_dev = jnp.asarray(self.ids.astype(np.int32),
                                        device=self.device)
        return self._ids_dev

    def live_rows(self) -> np.ndarray:
        """Indices of live rows, ascending (the iteration order)."""
        return np.flatnonzero(_np_unpack_bitmask(self.valid, self.length))

    def kill_row(self, row: int):
        """Tombstone one row: clear its validity bit (host + cached
        device mask dropped) and decrement the live count."""
        self.valid[row // 32] &= np.uint32(~np.uint32(1 << (row % 32)))
        self.live -= 1
        self._valid_dev = None

    def move_to(self, device):
        """Put the segment's device arrays on ``device`` (its cached
        device copies are rebuilt there on demand)."""
        self.words = jax.device_put(self.words, device)
        if self.hashes is not None:
            self.hashes = jax.device_put(self.hashes, device)
        self.device = device
        self._valid_dev = None
        self._ids_dev = None


def device_id(device):
    """A span attribute naming a chip: its id, or None when unplaced."""
    return None if device is None else device.id


def _empty_segment(cap: int, n_words: int, n_tables,
                   device=None) -> Segment:
    return Segment(
        words=jnp.zeros((cap, n_words), jnp.uint32, device=device),
        hashes=(jnp.zeros((cap, n_tables), jnp.uint32, device=device)
                if n_tables else None),
        ids=np.full(cap, -1, np.int64),
        valid=np.zeros(_packing.bitmask_width(cap), np.uint32),
        live=0, length=0, device=device)


class SegmentLogStore:
    """Mutable corpus of packed codes: append-only segment log + tombstones.

    All mutators bump ``generation`` (result-cache invalidation hook for
    the serving layer). The store holds *codes*; vector encoding lives in
    ``repro.index.engine.MutableAnnEngine``. ``devices`` (a sequence of
    ``jax.Device``) places segment ``s`` on ``devices[s % len(devices)]``
    (module docstring); None leaves placement to JAX.
    """

    def __init__(self, k: int, bits: int, *, band_spec: BandSpec = None,
                 tail_rows: int = 1024, impl: str = "auto",
                 registry: MetricsRegistry = None, devices=None):
        if tail_rows % 32:
            raise ValueError(f"tail_rows must be a multiple of 32, "
                             f"got {tail_rows}")
        if devices is not None and not len(devices):
            raise ValueError("devices must name at least one device")
        self.devices = tuple(devices) if devices is not None else None
        self.k = k
        self.bits = bits
        self.band_spec = band_spec.validate(k) if band_spec else None
        self.tail_rows = tail_rows
        self.impl = impl
        self.n_words = _packing.packed_width(k, bits)
        self.sealed: list[Segment] = []
        self.tail = self._new_tail()
        self.next_id = 0
        self.generation = 0
        self._by_id: dict[int, tuple[Segment, int]] = {}
        self._listeners: list = []
        self.registry = registry if registry is not None \
            else MetricsRegistry(enabled=True)
        self._c_appended = self.registry.counter("index.rows_appended")
        self._c_deleted = self.registry.counter("index.rows_deleted")
        self._c_seals = self.registry.counter("index.seals")
        self._g_live = self.registry.gauge("index.live_rows")
        self._g_dead = self.registry.gauge("index.dead_rows")
        self._g_livefrac = self.registry.gauge("index.live_fraction")
        self._g_segments = self.registry.gauge("index.segments")
        self._g_tail = self.registry.gauge("index.tail_fill")
        self._g_bytes = self.registry.gauge("index.resident_bytes")

    def _update_gauges(self):
        """Refresh the store-shape gauges after any mutation."""
        self._g_live.set(self.n_live)
        self._g_dead.set(self.n_rows - self.n_live)
        self._g_livefrac.set(self.n_live / self.n_rows
                             if self.n_rows else 1.0)
        self._g_segments.set(self.n_segments)
        self._g_tail.set(self.tail.length / self.tail_rows)
        self._g_bytes.set(self.nbytes)

    def _new_tail(self) -> Segment:
        return _empty_segment(
            self.tail_rows, self.n_words,
            self.band_spec.n_tables if self.band_spec else 0,
            device=self.device_at(len(self.sealed)))

    # -- placement -----------------------------------------------------------
    def device_at(self, pos: int):
        """The chip that segment ``pos`` of the log lives on (None when
        the store is unplaced)."""
        if self.devices is None:
            return None
        return self.devices[pos % len(self.devices)]

    def _place(self):
        """Move every segment whose log position changed (compaction,
        restore) onto the chip that position names."""
        if self.devices is None:
            return
        for pos, seg in enumerate(self.segments()):
            if seg.device != self.device_at(pos):
                seg.move_to(self.device_at(pos))

    def _gather(self, arrays: list) -> list:
        """Arrays of several segments, put on one chip (``devices[0]``)
        so they can be joined; an unplaced store returns them as-is."""
        if self.devices is None:
            return arrays
        return jax.device_put(arrays, self.devices[0])

    # -- geometry ------------------------------------------------------------
    @property
    def n_live(self) -> int:
        """Live (non-tombstoned) rows across all segments."""
        return len(self._by_id)

    @property
    def n_rows(self) -> int:
        """Resident rows, live or dead (excludes unwritten tail slots)."""
        return sum(s.length for s in self.segments())

    @property
    def n_segments(self) -> int:
        """Resident segments (sealed + the tail)."""
        return len(self.sealed) + 1

    @property
    def nbytes(self) -> int:
        """Resident device bytes (words + hashes + masks), full buffers."""
        total = 0
        for s in self.segments():
            total += s.words.size * 4 + s.valid.size * 4
            if s.hashes is not None:
                total += s.hashes.size * 4
        return total

    def segments(self) -> list[Segment]:
        """Iteration order: sealed segments in log order, then the tail."""
        return self.sealed + [self.tail]

    def __contains__(self, item_id: int) -> bool:
        return int(item_id) in self._by_id

    # -- mutation listeners --------------------------------------------------
    def add_listener(self, callback) -> "SegmentLogStore":
        """Subscribe ``callback(event: str, ids)`` to membership events:
        ``"delete"`` carries the external ids just tombstoned (int64
        array), ``"compact"`` carries None (external ids survive
        compaction unchanged). The shadow reservoir of
        ``repro.obs.quality`` subscribes here to stay tombstone-aware.
        Returns self."""
        self._listeners.append(callback)
        return self

    def _notify(self, event: str, ids):
        for cb in self._listeners:
            cb(event, ids)

    def take_codes(self, ids) -> np.ndarray:
        """int32 codes [m, k] of *live* external ids int [m] (the small
        per-id gather behind the quality audit; raises KeyError on a
        dead/unknown id)."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        rows = []
        for item in ids:
            seg, row = self._by_id[int(item)]
            rows.append(seg.words[row])
        if not rows:
            return np.zeros((0, self.k), np.int32)
        words = jnp.stack(self._gather(rows))
        return np.asarray(
            _packing.unpack_codes(words, self.bits, self.k), np.int32)

    # -- ingestion -----------------------------------------------------------
    def add_codes(self, codes, ids=None) -> np.ndarray:
        """Append int codes [m, k]; returns the external ids (int64 [m]).

        Auto-assigned ids continue from ``next_id``; explicit ids must
        not collide with a live id (use ``upsert_codes`` to replace).
        O(batch) device copy via the donated tail write.
        """
        shape = np.shape(codes)          # no copy/transfer, any array type
        if len(shape) != 2 or shape[1] != self.k:
            raise ValueError(f"codes {shape} != [m, {self.k}]")
        with span("store.append", rows=shape[0]):
            ids = self._prepare_ids(ids, shape[0])
            if shape[0] == 0:
                return ids
            codes = jnp.asarray(codes)
            words = _ops.pack_codes(codes, self.bits, impl=self.impl)
            hashes = (band_hashes(codes, self.band_spec)
                      if self.band_spec else None)
            return self._append(words, hashes, ids)

    def add_words(self, words, ids=None) -> np.ndarray:
        """Append already-packed uint32 rows [m, W] (the fused-ingest
        path, ``repro.encode``): same id rules and O(batch) donated tail
        write as ``add_codes``, but int32 codes for the batch never
        exist on device — except, with a ``band_spec``, a chunk-local
        unpack to compute the band hashes (O(batch), never O(corpus))."""
        shape = np.shape(words)          # no copy/transfer, any array type
        if len(shape) != 2 or shape[1] != self.n_words:
            raise ValueError(f"words {shape} != [m, {self.n_words}]")
        with span("store.append", rows=shape[0]):
            ids = self._prepare_ids(ids, shape[0])
            if shape[0] == 0:
                return ids
            words = jnp.asarray(words, jnp.uint32)
            if self.band_spec:
                hashes = band_hashes(
                    _packing.unpack_codes(words, self.bits, self.k),
                    self.band_spec)
            else:
                hashes = None
            return self._append(words, hashes, ids)

    def _prepare_ids(self, ids, m: int) -> np.ndarray:
        """Validate/auto-assign a batch's external ids — runs before any
        device work so bad batches are rejected for free."""
        if ids is None:
            ids = np.arange(self.next_id, self.next_id + m, dtype=np.int64)
        else:
            ids = np.asarray(ids, np.int64)
            if ids.shape != (m,):
                raise ValueError(f"ids {ids.shape} != ({m},)")
            if np.unique(ids).size != m:
                raise ValueError("duplicate ids within one batch")
            clash = [int(i) for i in ids if int(i) in self._by_id]
            if clash:
                raise ValueError(f"ids already live (upsert instead): "
                                 f"{clash[:5]}")
        if m and (ids.min() < 0 or ids.max() >= 2 ** 31 - 1):
            raise ValueError("ids must fit int32 (device id gather)")
        return ids

    def _append(self, words, hashes, ids) -> np.ndarray:
        """Shared append tail: chunked donated tail writes (ids already
        validated), seal-on-full, generation bump."""
        m = words.shape[0]
        pos = 0
        while pos < m:
            t = min(self.tail_rows - self.tail.length, m - pos)
            self._write_tail(words, hashes, ids, pos, t)
            pos += t
            if self.tail.length == self.tail_rows:
                self._seal_tail()
        self.next_id = max(self.next_id, int(ids.max()) + 1)
        self.generation += 1
        self._c_appended.inc(m)
        self._update_gauges()
        return ids

    def _write_tail(self, words, hashes, ids, pos: int, t: int):
        tail = self.tail
        start = tail.length
        # pad the chunk to a power of two when it fits, so the donated
        # write executable compiles O(log tail_rows) times, not O(sizes)
        tp = 1 << max(t - 1, 0).bit_length()
        if start + tp > self.tail_rows:
            tp = t
        chunk = jax.lax.dynamic_slice_in_dim(words, pos, t, 0)
        if tp > t:      # zero rows land on not-yet-valid slots
            chunk = jnp.pad(chunk, ((0, tp - t), (0, 0)))
        if tail.device is not None:
            chunk = jax.device_put(chunk, tail.device)
        tail.words = _write_rows(tail.words, chunk, start)
        if hashes is not None:
            hc = jax.lax.dynamic_slice_in_dim(hashes, pos, t, 0)
            if tp > t:
                hc = jnp.pad(hc, ((0, tp - t), (0, 0)))
            if tail.device is not None:
                hc = jax.device_put(hc, tail.device)
            tail.hashes = _write_rows(tail.hashes, hc, start)
        with span("store.id_map", rows=t):
            rows = np.arange(start, start + t)
            tail.ids[start:start + t] = ids[pos:pos + t]
            np.bitwise_or.at(tail.valid, rows // 32,
                             np.uint32(1) << (rows % 32).astype(np.uint32))
            self._by_id.update(
                (int(item), (tail, start + j))
                for j, item in enumerate(ids[pos:pos + t]))
        tail.live += t
        tail.length += t
        tail._valid_dev = None
        tail._ids_dev = None

    def _seal_tail(self):
        """The full tail becomes a sealed segment as-is (no copy: the id
        map keys on the Segment object, which just moves lists)."""
        with span("store.seal", segment=len(self.sealed),
                  device=device_id(self.tail.device)):
            self.sealed.append(self.tail)
            self.tail = self._new_tail()
            self._c_seals.inc()

    # -- deletes / upserts ---------------------------------------------------
    def delete(self, ids, strict: bool = True) -> int:
        """Tombstone external ids. Returns the number of rows killed;
        unknown ids raise (``strict``) or are ignored. Strict deletes are
        all-or-nothing: ids are validated before anything is tombstoned,
        so a raise leaves the store (and its generation) untouched."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        if strict:
            dead = [int(i) for i in ids if int(i) not in self._by_id]
            if dead:
                raise KeyError(f"ids not live: {dead[:5]}")
        killed = 0
        killed_ids = []
        for item in ids:
            loc = self._by_id.pop(int(item), None)
            if loc is None:
                continue
            seg, row = loc
            seg.kill_row(row)
            killed_ids.append(int(item))
            killed += 1
        if killed:
            self.generation += 1
            self._c_deleted.inc(killed)
            self._update_gauges()
            self._notify("delete", np.asarray(killed_ids, np.int64))
        return killed

    def upsert_codes(self, ids, codes) -> np.ndarray:
        """Replace-or-insert: tombstone each id's current row (if live),
        append the new version under the *same* external id. The batch is
        validated *before* the tombstones, so a bad upsert never loses
        the old versions."""
        ids = np.atleast_1d(np.asarray(ids, np.int64))
        codes = jnp.asarray(codes)
        if codes.ndim != 2 or codes.shape != (ids.size, self.k):
            raise ValueError(f"codes {codes.shape} != [{ids.size}, "
                             f"{self.k}]")
        if np.unique(ids).size != ids.size:
            raise ValueError("duplicate ids within one batch")
        if ids.size and (ids.min() < 0 or ids.max() >= 2 ** 31 - 1):
            raise ValueError("ids must fit int32 (device id gather)")
        self.delete([i for i in ids if int(i) in self._by_id])
        return self.add_codes(codes, ids=ids)

    # -- live-row views (oracle / compaction / snapshot) ---------------------
    def live_ids(self) -> np.ndarray:
        """External ids of live rows in iteration order, int64 [n_live]."""
        out = [seg.ids[seg.live_rows()] for seg in self.segments()]
        return (np.concatenate(out) if out
                else np.zeros(0, np.int64))

    def live_words(self):
        """Packed live rows in iteration order -> uint32 [n_live, W]."""
        parts = [jnp.take(seg.words, jnp.asarray(rows), axis=0)
                 for seg in self.segments()
                 if (rows := seg.live_rows()).size]
        if not parts:
            return jnp.zeros((0, self.n_words), jnp.uint32)
        return jnp.concatenate(self._gather(parts))

    def live_codes(self):
        """Unpacked live rows [n_live, k] int32 (fresh-build oracle)."""
        return _packing.unpack_codes(self.live_words(), self.bits, self.k)

    def stats(self) -> dict:
        """Operational counters: rows (live/dead), segments, tail fill,
        resident bytes, generation."""
        return {"n_live": self.n_live, "n_rows": self.n_rows,
                "n_dead": self.n_rows - self.n_live,
                "n_segments": self.n_segments,
                "tail_len": self.tail.length, "nbytes": self.nbytes,
                "generation": self.generation}
