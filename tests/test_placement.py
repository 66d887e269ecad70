"""Segment placement across devices: a ``MutableAnnEngine`` placed over
four devices, served through ``AnnService``, answers bit for bit as the
same rows in one device's segment log and as the ``kernels/ref.py``
oracles over the live rows, through deletes, an upsert, a part-filled
tail, compaction and a snapshot round trip.

One subprocess with four forced host devices (the main test process keeps
its one device) runs every case and reports each one; the cases below
read its report."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import json
import os
import sys
import tempfile
import traceback
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, "src")
import numpy as np
import jax
import jax.numpy as jnp

from repro.ann.engine import rho_scored
from repro.core import packing as PK
from repro.core.sketch import CodedRandomProjection, SketchConfig
from repro.index import CompactionPolicy, MutableAnnEngine
from repro.kernels import ref
from repro.serve.ann_service import AnnService, AnnServiceConfig

DEVS = jax.devices()
assert len(DEVS) == 4, DEVS
D, K, BITS, TAIL, TOP, M = 16, 64, 2, 256, 5, 8
rng = np.random.default_rng(5)


def rows(n):
    x = rng.normal(size=(n, D)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


CORPUS = rows(5 * TAIL + 100)        # 5 sealed segments, tail part-filled
KILL = np.arange(3, CORPUS.shape[0], 7)
UPSERT_IDS, UPSERT_X = np.array([10, 700]), rows(2)
QUERIES = np.concatenate([CORPUS[::97][:6] + 0.05 * rows(6), rows(2)])


def engine(devices):
    crp = CodedRandomProjection(SketchConfig(k=K, scheme="2bit", w=0.75), D)
    eng = MutableAnnEngine(crp, band_spec=None, tail_rows=TAIL,
                           devices=devices)
    load = service(eng, False)
    load.bulk_load(CORPUS, chunk_rows=128)
    load.delete(KILL)
    load.upsert(UPSERT_IDS, UPSERT_X)
    return eng


def service(eng, scored):
    return AnnService(eng, AnnServiceConfig(
        top_k=TOP, buckets=(8,), cache_size=0, scored=scored,
        rerank_m=M if scored else 0))


def answer(svc):
    tickets = [svc.submit(q) for q in QUERIES]
    out = svc.flush()
    return (np.stack([out[t][0] for t in tickets]),
            np.stack([out[t][1] for t in tickets]))


def placement_faults(store):
    bad = []
    for s, seg in enumerate(store.segments()):
        want = {DEVS[s % 4]}
        got = [seg.words.devices(), seg.valid_dev().devices(),
               seg.ids_dev().devices()]
        if seg.device != DEVS[s % 4] or any(g != want for g in got):
            bad.append((s, str(seg.device), [str(g) for g in got]))
    return bad


def same(a, b, what):
    for x, y, name in zip(a, b, ("ids", "rho")):
        if not np.array_equal(x, y):
            raise AssertionError(f"{what}: {name} differ\n{x}\n{y}")


def oracle(eng, scored):
    # brute force over the live rows in iteration order (log order,
    # then row order): exact takes the TOP best counts; scored takes per
    # segment the M best counts, scores them with the query tables and
    # keeps the TOP best scores. Ties go to the earlier live row.
    store = eng.store
    q_codes = eng.encode_queries(jnp.asarray(QUERIES))
    q_words = PK.pack_codes(q_codes, BITS)
    words = store.live_words()
    counts = np.asarray(ref.packed_collision_ref(q_words, words, BITS, K))
    live_ids = store.live_ids()
    if not scored:
        pos = np.argsort(-counts, axis=1, kind="stable")[:, :TOP]
        vals = np.take_along_axis(counts, pos, 1)
        return live_ids[pos], np.asarray(eng._rho(jnp.asarray(vals)))
    bounds = np.cumsum([0] + [seg.live for seg in store.segments()])
    cand = np.concatenate(
        [lo + np.argsort(-counts[:, lo:hi], axis=1, kind="stable")[:, :M]
         for lo, hi in zip(bounds[:-1], bounds[1:]) if hi > lo], axis=1)
    cand.sort(axis=1)                   # candidates in iteration order
    scores = np.asarray(ref.lut_scores_rowwise_ref(
        eng.rank_tables.query_tables(q_codes),
        jnp.take(words, jnp.asarray(cand), axis=0), BITS))
    pos = np.argsort(-scores, axis=1, kind="stable")[:, :TOP]
    best = np.take_along_axis(scores, pos, 1)
    ids = live_ids[np.take_along_axis(cand, pos, 1)]
    return ids, np.asarray(rho_scored(eng.rank_tables, jnp.asarray(ids),
                                      jnp.asarray(best)))


report = {}


def case(name):
    def wrap(fn):
        try:
            fn()
            report[name] = "ok"
        except Exception:
            report[name] = traceback.format_exc()[-3000:]
    return wrap


placed_eng, plain_eng = engine(DEVS), engine(None)
placed = {s: service(placed_eng, s) for s in (False, True)}
plain = {s: service(plain_eng, s) for s in (False, True)}
got = {s: answer(placed[s]) for s in (False, True)}
want = {s: answer(plain[s]) for s in (False, True)}
pstore = placed_eng.store


@case("placement")
def _():
    assert len(pstore.sealed) == 5 and 0 < pstore.tail.length < TAIL
    assert not placement_faults(pstore), placement_faults(pstore)
    assert plain_eng.store.devices is None
    assert all(s.device is None for s in plain_eng.store.segments())


@case("exact_vs_unplaced")
def _():
    same(got[False], want[False], "exact")


@case("exact_vs_oracle")
def _():
    same(got[False], oracle(placed_eng, False), "exact oracle")


@case("scored_vs_unplaced")
def _():
    same(got[True], want[True], "scored")


@case("scored_vs_oracle")
def _():
    same(got[True], oracle(placed_eng, True), "scored oracle")


@case("shard_counters")
def _():
    for s in (False, True):
        st, st0 = placed[s].stats, plain[s].stats
        assert st["shard_put_bytes"] > 0 and st["shard_gather_bytes"] > 0, \
            dict(st)
        assert st0["shard_put_bytes"] == st0["shard_gather_bytes"] == 0
    # scored puts the words and f32 tables of 8 queries on 3 other
    # chips; 5 of the 6 segments (the tail's chip is 1) return
    # [8, TOP] f32 scores and int32 ids from chips 1-3
    st = placed[True].stats
    assert st["shard_put_bytes"] == 3 * (8 * 4 * 4 + 8 * K * 4 * 4)
    assert st["shard_gather_bytes"] == 4 * 8 * TOP * 8


@case("compact")
def _():
    # sealed segments 0-3 merge into one on chip 0; segment 4 and the
    # tail shift to positions 1 and 2, so both move chips
    policy = CompactionPolicy(target_rows=4 * TAIL)
    rep = placed_eng.compact(policy)
    plain_eng.compact(policy)
    assert (rep["segments_before"], rep["segments_after"]) == (5, 2), rep
    assert not placement_faults(pstore), placement_faults(pstore)
    for s in (False, True):
        mine = answer(placed[s])
        same(mine, answer(plain[s]), f"compact scored={s}")
        same(mine, oracle(placed_eng, s), f"compact oracle scored={s}")


@case("restore")
def _():
    with tempfile.TemporaryDirectory() as tmp:
        placed_eng.save(tmp, 1)
        back = MutableAnnEngine.restore(placed_eng.sketcher, tmp,
                                        devices=DEVS)
    assert not placement_faults(back.store)
    for s in (False, True):
        same(answer(service(back, s)), answer(plain[s]),
             f"restore scored={s}")
    service(back, False).add(rows(TAIL))    # a seal and a placed tail
    assert len(back.store.sealed) == 3
    assert not placement_faults(back.store), placement_faults(back.store)


print("REPORT " + json.dumps(report))
"""

CASES = ["placement", "exact_vs_unplaced", "exact_vs_oracle",
         "scored_vs_unplaced", "scored_vs_oracle", "shard_counters",
         "compact", "restore"]


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=600)
    lines = [ln for ln in res.stdout.splitlines() if ln.startswith("REPORT ")]
    assert lines, f"STDOUT:\n{res.stdout[-3000:]}\nSTDERR:\n{res.stderr[-3000:]}"
    return json.loads(lines[-1][len("REPORT "):])


@pytest.mark.parametrize("name", CASES)
def test_placed_engine(report, name):
    assert report.get(name) == "ok", report.get(name, "case did not run")
