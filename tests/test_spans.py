"""Program spans on the profiler's clock: with no ``Tracer`` installed,
``span`` still writes a ``jax.profiler`` annotation, so the serving
flush, the ingest call and garbage collections land in the device
trace's host plane, nested as they ran; plus the queue-wait counter."""
import gc
import glob
import os
import time
import warnings

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.sketch import CodedRandomProjection, SketchConfig
from repro.index import MutableAnnEngine
from repro.obs import Tracer, install_gc_spans, span, tracing_active
from repro.serve.ann_service import AnnService, AnnServiceConfig

D, K = 16, 16


def _service(tail_rows=64, devices=None, **cfg):
    crp = CodedRandomProjection(SketchConfig(k=K, scheme="2bit", w=0.75), D)
    eng = MutableAnnEngine(crp, band_spec=None, tail_rows=tail_rows,
                           devices=devices)
    return AnnService(eng, AnnServiceConfig(top_k=3, buckets=(1, 4),
                                            **cfg))


def _rows(n, seed=0):
    x = np.random.default_rng(seed).normal(size=(n, D)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


class _Profile:
    """Host events of the profiled block: ``events`` holds (name,
    start_ns, end_ns, stats dict, thread line)."""

    def __init__(self, path):
        self.path = path
        self.events = []

    def __enter__(self):
        jax.profiler.start_trace(self.path)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        (f,) = glob.glob(os.path.join(self.path, "**", "*.xplane.pb"),
                         recursive=True)
        pd = jax.profiler.ProfileData.from_file(f)
        with warnings.catch_warnings():     # event_stats has no __module__
            warnings.simplefilter("ignore", DeprecationWarning)
            for plane in pd.planes:
                if plane.name.startswith("/host:"):
                    for i, line in enumerate(plane.lines):
                        for e in line.events:
                            self.events.append(
                                (e.name, e.start_ns,
                                 e.start_ns + e.duration_ns,
                                 dict(e.stats), (plane.name, i)))
        return False

    def named(self, name):
        return [e for e in self.events if e[0] == name]

    def inside(self, outer, name):
        """Events called ``name`` on ``outer``'s thread within it."""
        return [e for e in self.named(name) if e[4] == outer[4]
                and outer[1] <= e[1] and e[2] <= outer[2]]


def test_span_without_tracer_lands_on_host_plane(tmp_path):
    assert not tracing_active()
    with _Profile(str(tmp_path)) as prof:
        with span("outer", meta=True, trace_id=7) as sp:
            with span("inner", rows=3):
                sp.sync(jnp.ones(4) * 2)
            with span("inner"):
                pass
    (outer,) = prof.named("outer")
    assert outer[3] == {"trace_id": 7}
    inner = prof.inside(outer, "inner")
    assert len(inner) == 2
    assert all(e[3] == {} for e in inner)       # metadata on outer spans only
    assert inner[0][2] <= inner[1][1]           # in the order they ran


def test_flush_spans_nest_inside_serve_flush(tmp_path):
    svc = _service(cache_size=8)
    svc.bulk_load(_rows(40), chunk_rows=16)
    with _Profile(str(tmp_path)) as prof:
        for row in _rows(3, seed=1):
            svc.submit(row)
        svc.flush()
    assert len(prof.named("serve.submit")) == 3
    (flush,) = prof.named("serve.flush")
    assert flush[3]["pending"] == 3 and flush[3]["trace_id"] >= 1
    stages = ["serve.batch", "serve.encode", "serve.cache_key",
              "engine.search", "serve.fetch"]
    found = [prof.inside(flush, name) for name in stages]
    assert [len(f) for f in found] == [1] * len(stages)
    starts = [f[0][1] for f in found]
    assert starts == sorted(starts)             # in pipeline order
    assert prof.inside(found[3][0], "search.coarse")   # one per segment


def test_placed_search_opens_place_and_gather():
    """A placed engine puts the query on its segments' chips
    (``search.place``) and gathers their results (``search.gather``)
    inside ``engine.search``; an unplaced one opens neither."""
    found = {}
    for placed in (False, True):
        svc = _service(cache_size=0,
                       devices=jax.devices()[:1] if placed else None)
        svc.bulk_load(_rows(150), chunk_rows=32)    # 2 sealed + tail
        with Tracer() as tr:
            for row in _rows(3, seed=1):
                svc.submit(row)
            svc.flush()
        (search,) = [e for e in tr.events if e["name"] == "engine.search"]
        inside = [e["name"] for e in tr.events
                  if search["ts"] <= e["ts"]
                  and e["ts"] + e["dur"] <= search["ts"] + search["dur"]]
        found[placed] = [inside.count(name) for name in
                         ("search.place", "search.gather", "search.coarse")]
    assert found == {False: [0, 0, 3], True: [1, 1, 3]}


def test_segment_spans_name_their_device():
    """``search.fused``, ``search.coarse`` and ``store.seal`` carry the
    chip of their segment (None when unplaced)."""
    dev = jax.devices()[0]
    for devices, scored in ((None, False), ([dev], False), ([dev], True)):
        svc = _service(cache_size=0, scored=scored, devices=devices)
        with Tracer() as tr:
            svc.bulk_load(_rows(80), chunk_rows=16)
            svc.submit(_rows(1, seed=3)[0])
            svc.flush()
        seg = "search.fused" if scored else "search.coarse"
        spans = [e for e in tr.events if e["name"] in (seg, "store.seal")]
        assert [e["name"] for e in spans].count(seg) == 2
        assert [e["name"] for e in spans].count("store.seal") == 1
        want = None if devices is None else dev.id
        assert all(e["args"]["device"] == want for e in spans)
    placed = [e for e in tr.events if e["name"] == "search.place"]
    assert [e["args"]["chips"] for e in placed] == [1]


def test_bulk_load_spans_nest_inside_store_append(tmp_path):
    svc = _service(tail_rows=32)
    with _Profile(str(tmp_path)) as prof:
        svc.bulk_load(_rows(48), chunk_rows=16)     # fills one tail
    (ingest,) = prof.named("encode.ingest")
    appends = prof.inside(ingest, "store.append")
    assert len(appends) == len(prof.inside(ingest, "encode.chunk")) == 3
    assert all(len(prof.inside(a, "store.id_map")) == 1 for a in appends)
    (seal,) = prof.named("store.seal")
    assert any(a[1] <= seal[1] and seal[2] <= a[2] for a in appends)


def test_gc_collect_emits_runtime_gc(tmp_path):
    _service()
    n = len(gc.callbacks)
    _service()
    install_gc_spans()                          # the hook installs once
    assert len(gc.callbacks) == n
    with _Profile(str(tmp_path)) as prof:
        gc.collect()
    full = [e for e in prof.named("runtime.gc")
            if e[3].get("generation") == 2]
    assert full and all(e[2] > e[1] for e in full)


def test_queue_wait_counts_submit_to_flush_and_skips_probes():
    svc = _service(cache_size=0)
    svc.bulk_load(_rows(40), chunk_rows=16)
    assert svc.stats["queue_wait_s"] == 0
    rows = _rows(2, seed=2)
    for row in rows:
        svc.submit(row)
    time.sleep(0.05)
    svc.flush()
    waited = svc.stats["queue_wait_s"]
    assert waited >= 2 * 0.05                   # both tickets waited
    svc.probe_search(rows[0])
    assert svc.stats["queue_wait_s"] == waited
    assert svc.registry.counters["serve.probe.queue_wait_s"].value > 0
