"""Smoke run of the main path on a TPU: bulk ingest -> served search ->
classify, every answer checked against the jnp oracles on the same chip.

    python chip_smoke.py [--seed 0] [--rows-log2 24]      # one chip
    python chip_smoke.py --chips 4                        # sharded search

One chip: a dense-embedding deployment at the width of ann-benchmarks'
100-dimensional angular set (d=100, unit rows, 2-bit codes, k=256,
w=0.75) is bulk-loaded through ``AnnService.bulk_load`` (2^24 rows =
1 GiB of packed codes by default, generated from ``--seed`` in chunks on
the device), then answers flushes of served queries — unscored exact
search (masked collision top-k) and scored search (fused masked scored
kernel) — with deletes between flushes, and finally classifies through a
model fitted by ``fit_store``. Every answer is compared with the same
request served at ``impl="ref"`` (the ``kernels/ref.py`` oracles) on the
same device: ids, rho, counts, scores, fitted weights and margins, bit
for bit. Any mismatch or error exits non-zero; no phase catches and
carries on.

``--chips 4`` runs only the sharded path: the corpus row-sharded over a
4-device mesh through ``AnnEngine.search_sharded``, compared bit for bit
with single-device ``AnnEngine.search`` on the same corpus, unscored and
scored.

The lines before the last are a smoke log, not a benchmark: wall times
include compilation and host work. The last line is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``. Without a TPU
the script exits non-zero before doing anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax

D = 100                  # ann-benchmarks angular word vectors (GloVe-100)
K, SCHEME, W = 256, "2bit", 0.75
TOP_K = 10
QUERIES, FLUSHES = 32, 3     # served queries per flush, flushes per mode


def chunk_rows(rows_log2: int) -> int:
    """Rows per generated corpus chunk: 2^20, and at least 4 chunks, so
    a small rehearsal still fills 4 segments."""
    return 1 << max(min(20, rows_log2 - 2), 0)


def log(msg: str):
    print(f"[smoke] {msg}", flush=True)


class Phases:
    """Wall-clock seconds per phase, and backend compile seconds."""

    def __init__(self):
        self.t = {}
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if "backend_compile" in event:
            self.compile_s += duration

    def run(self, name: str, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        self.t[name] = time.perf_counter() - t0
        log(f"phase {name}: {self.t[name]:.3f} s wall "
            f"(compile so far {self.compile_s:.3f} s)")
        return out


def _unit_rows(key, n: int):
    import jax.numpy as jnp
    x = jax.random.normal(key, (n, D), jnp.float32)
    return x / jnp.linalg.norm(x, axis=1, keepdims=True)


def corpus_chunk(seed: int, i: int, n: int):
    """Chunk i of the corpus: n unit rows, made on the device."""
    return _unit_rows(jax.random.fold_in(jax.random.PRNGKey(seed), i), n)


def queries_near(seed: int, base, n: int, noise: float = 0.3):
    """n queries: the first n rows of ``base`` moved by seeded noise and
    renormalized, so every query has true near neighbours."""
    import jax.numpy as jnp
    eps = jax.random.normal(jax.random.PRNGKey(seed + 7919), (n, D))
    q = base[:n] + noise * eps / jnp.sqrt(D)
    return q / jnp.linalg.norm(q, axis=1, keepdims=True)


def same(name: str, got, want):
    """Bit-for-bit equality of two arrays (float compared as bits)."""
    import numpy as np
    g, w = np.asarray(got), np.asarray(want)
    if g.shape != w.shape or g.dtype != w.dtype:
        raise AssertionError(f"{name}: {g.shape}/{g.dtype} vs "
                             f"{w.shape}/{w.dtype}")
    gap = ""
    if g.dtype.kind == "f":
        with np.errstate(invalid="ignore"):
            gap = f" (max |difference| {np.nanmax(np.abs(g - w)):.3e})"
        g, w = g.view(f"u{g.itemsize}"), w.view(f"u{w.itemsize}")
    bad = int(np.sum(g != w))
    if bad:
        raise AssertionError(f"{name}: {bad} of {g.size} entries differ "
                             f"from the oracle{gap}")


def sketcher(seed: int):
    from repro.core.sketch import CodedRandomProjection, SketchConfig
    return CodedRandomProjection(
        SketchConfig(k=K, scheme=SCHEME, w=W, seed=seed), d=D)


# -- one chip: ingest -> search -> classify -----------------------------------

def ingest(svc, seed: int, rows_log2: int):
    rows = chunk_rows(rows_log2)
    for i in range((1 << rows_log2) // rows):
        svc.bulk_load(corpus_chunk(seed, i, rows), chunk_rows=1 << 16)
    store = svc.engine.store
    log(f"rows ingested: {store.n_rows} "
        f"({store.n_rows * store.n_words * 4 / 2**30:.3f} GiB of packed "
        f"codes); live rows {store.n_live}")
    held = sum(1 for s in store.segments() if s.length)
    log(f"segments: {store.n_segments} ({held} holding rows, tail "
        f"capacity {store.tail_rows} rows)")
    return store


def search(svc, svc_ref, queries, flushes: int, seed: int, label: str):
    """Serve ``flushes`` flushes of the same queries through ``svc`` and
    ``svc_ref``; tombstone each answer's best hit (and a few random live
    rows) between flushes."""
    import numpy as np
    rng = np.random.default_rng(seed)
    deleted = set()
    for f in range(flushes):
        tk = [svc.submit(q) for q in queries]
        tr = [svc_ref.submit(q) for q in queries]
        got, want = svc.flush(), svc_ref.flush()
        ids = np.stack([got[t][0] for t in tk])
        same(f"{label} flush {f} ids", ids,
             np.stack([want[t][0] for t in tr]))
        same(f"{label} flush {f} rho", np.stack([got[t][1] for t in tk]),
             np.stack([want[t][1] for t in tr]))
        if deleted & set(ids.ravel().tolist()):
            raise AssertionError(f"{label}: a deleted id was answered")
        if (ids < 0).any():
            raise AssertionError(f"{label}: empty slots in a full corpus")
        live = svc.engine.store.n_live
        kill = set(ids[:, 0].tolist())
        kill |= set(rng.choice(live, size=64, replace=False).tolist())
        kill = [i for i in kill if i in svc.engine.store]
        svc.delete(kill)
        deleted |= set(kill)
        log(f"{label} flush {f}: {len(queries)} queries match the oracle; "
            f"deleted {len(kill)} rows (first query's hits "
            f"{ids[0, :4].tolist()})")


def raw_kernels(engine, q_codes, impl: str):
    """Counts and scores straight from the masked kernels on the first
    segment (full size), against the oracles: the values behind rho."""
    from repro.ann.engine import resolve_query_tables
    from repro.kernels import ops
    seg = engine.store.segments()[0]
    bits = engine.store.bits

    def tabs(dtype):
        t, s = resolve_query_tables(engine.rank_tables, q_codes, dtype)
        return (t,) if s is None else (t, s)

    qw = ops.pack_codes(q_codes, bits, impl=impl)
    same("pack_codes", qw, ops.pack_codes(q_codes, bits, impl="ref"))
    args = (qw, seg.words, seg.valid_dev(), bits, K)
    for name, run in (
            ("counts", lambda i: ops.packed_topk_masked(*args, TOP_K,
                                                        impl=i)),
            ("scores f32", lambda i: ops.fused_scored_topk_masked(
                qw, *tabs("auto"), seg.words, seg.valid_dev(), bits, K,
                64, TOP_K, scales=None, impl=i)),
            ("scores int8", lambda i: ops.fused_scored_topk_masked(
                qw, tabs("int8")[0], seg.words, seg.valid_dev(), bits, K,
                64, TOP_K, scales=tabs("int8")[1], impl=i))):
        (gv, gi), (wv, wi) = run(impl), run("ref")
        same(f"segment 0 {name}", gv, wv)
        same(f"segment 0 {name} rows", gi, wi)
        log(f"segment 0 ({seg.cap} rows) {name}: kernel == oracle")


def classify(svc, svc_ref, seed: int, n: int, impl: str):
    """fit_store on the packed codes of corpus chunk 0 (its n rows open
    segment 0, tombstones or not) with labels from a seeded hyperplane,
    then classify held-out vectors."""
    import jax.numpy as jnp
    import numpy as np
    from repro.ann.store import CodeStore
    from repro.learn import LearnConfig, fit_store
    engine = svc.engine
    x = corpus_chunk(seed, 0, n)
    h = jax.random.normal(jax.random.PRNGKey(seed + 104729), (D,))
    y = jnp.where(x @ h >= 0, 1, -1)
    store = CodeStore.from_words(engine.store.segments()[0].words[:n], K,
                                 engine.store.bits)
    models = {}
    for i in (impl, "ref"):
        cfg = LearnConfig(steps=40, batch=min(8192, n), impl=i, seed=seed)
        models[i] = fit_store(store, y, engine.sketcher, cfg)
    same("fitted tables", models[impl].tables, models["ref"].tables)
    same("fitted bias", models[impl].bias, models["ref"].bias)
    log(f"fit_store: {n} rows, 40 steps; weights == oracle-fitted weights")
    svc.set_classifier(models[impl])
    svc_ref.set_classifier(models[impl])
    held = _unit_rows(jax.random.PRNGKey(seed + 15485863), 768)
    truth = np.where(np.asarray(held @ h) >= 0, 1, -1)
    acc = []
    for b in range(3):
        xb = held[b * 256:(b + 1) * 256]
        labels, margins = svc.classify(xb)
        want_l, want_m = svc_ref.classify(xb)
        same(f"classify {b} labels", labels, want_l)
        same(f"classify {b} margins", margins, want_m)
        acc.append(float(np.mean(labels == truth[b * 256:(b + 1) * 256])))
    log(f"classify: 3 x 256 vectors match the oracle; hyperplane accuracy "
        f"{np.mean(acc):.4f}")
    if np.mean(acc) < 0.6:
        raise AssertionError(f"classifier accuracy {np.mean(acc)} is at "
                             f"chance: the fitted model is broken")


def one_chip(args, impl: str = "auto"):
    from repro.index.engine import MutableAnnEngine
    from repro.serve import AnnService, AnnServiceConfig
    ph = Phases()
    sk = sketcher(args.seed)
    rows = chunk_rows(args.rows_log2)
    tail = 1 << (args.rows_log2 - 2)     # 4 sealed segments
    engine = MutableAnnEngine(sk, band_spec=None, tail_rows=tail, impl=impl)
    svc = AnnService(engine, AnnServiceConfig(impl=impl))
    store = ph.run("ingest", ingest, svc, args.seed, args.rows_log2)
    q = queries_near(args.seed, corpus_chunk(args.seed, 0, rows),
                     QUERIES)
    codes = engine.encode_queries(q, impl=impl)
    same("query codes", codes, engine.encode_queries(q, impl="ref"))
    ph.run("raw kernels", raw_kernels, engine, codes[:16], impl)
    for scored in (False, True):
        label = "scored" if scored else "unscored"
        svc_k = AnnService(engine, AnnServiceConfig(impl=impl,
                                                    scored=scored))
        svc_r = AnnService(engine, AnnServiceConfig(
            impl="ref", scored=scored, buckets=(1, 8, 16)))
        ph.run(f"search {label}", search, svc_k, svc_r, q, FLUSHES,
               args.seed + int(scored), label)
    svc_r = AnnService(engine, AnnServiceConfig(impl="ref",
                                                buckets=(1, 8, 16)))
    ph.run("classify", classify, svc, svc_r, args.seed, rows, impl)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"live rows after deletes: {store.n_live} of {store.n_rows}")
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")
    log(f"backend compile seconds: {ph.compile_s:.3f}")
    log("phase seconds: " + json.dumps(
        {k: round(v, 3) for k, v in ph.t.items()}))


# -- four chips: sharded search vs single-device search -----------------------

def four_chips(args, impl: str = "auto"):
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.ann.bands import BandSpec, band_hashes
    from repro.ann.engine import AnnEngine
    from repro.ann.store import CodeStore
    from repro.core.packing import unpack_codes
    n_dev = len(jax.devices())
    if n_dev != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {n_dev}")
    ph = Phases()
    sk = sketcher(args.seed)
    enc = sk.stream_encoder()
    bands = BandSpec()
    rows = chunk_rows(args.rows_log2)

    def build():
        words, hashes = [], []
        for i in range((1 << args.rows_log2) // rows):
            w = enc.encode_packed(corpus_chunk(args.seed, i, rows),
                                  impl=impl)
            words.append(w)
            hashes.append(band_hashes(unpack_codes(w, sk.spec.bits, K),
                                      bands))
        return AnnEngine(sk, CodeStore.from_words(jnp.concatenate(words),
                                                  K, sk.spec.bits),
                         bands, db_band_hashes=jnp.concatenate(hashes))

    engine = ph.run("ingest", build)
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    sharded = engine.store.shard(mesh, "data")
    per_dev = {str(s.device): s.data.nbytes
               for s in sharded.words.addressable_shards}
    log(f"rows: {engine.n}; bytes per device: {json.dumps(per_dev)}")
    if len(per_dev) != 4 or len(set(per_dev.values())) != 1 or \
            sum(per_dev.values()) != engine.store.words.nbytes:
        raise AssertionError("corpus is not split evenly over 4 devices")
    q = queries_near(args.seed, corpus_chunk(args.seed, 0, rows),
                     QUERIES)
    # full re-rank (rerank_m = n): the per-shard coarse quota and the
    # global one then admit the same rows, so the two searches share one
    # semantics and must agree bit for bit
    for label, kw in (("unscored", {}),
                      ("scored", dict(scored=True, rerank_m=engine.n))):
        got = ph.run(f"sharded {label}", lambda: jax.block_until_ready(
            engine.search_sharded(q, mesh, top_k=TOP_K, impl=impl, **kw)))
        want = ph.run(f"single {label}", lambda: jax.block_until_ready(
            engine.search(q, TOP_K, impl=impl, **kw)))
        same(f"sharded {label} ids", got[0], want[0])
        same(f"sharded {label} rho", got[1], want[1])
        log(f"sharded {label}: {len(q)} queries equal single-device "
            f"search (first query {np.asarray(got[0][0, :4]).tolist()})")
    log(f"backend compile seconds: {ph.compile_s:.3f}")
    log("phase seconds: " + json.dumps(
        {k: round(v, 3) for k, v in ph.t.items()}))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows-log2", type=int, default=24,
                    help="corpus rows = 2**this (default 2^24 = 1 GiB)")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.runtime import enable_compile_cache
    log(f"smoke run (not a benchmark) on {dev.device_kind} x "
        f"{len(jax.devices())}; compile cache {enable_compile_cache()}")
    (four_chips if args.chips == 4 else one_chip)(args)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
