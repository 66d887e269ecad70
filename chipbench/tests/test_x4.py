"""The four-chip cell's pieces that run without four chips: the reference
spread over devices against the plain one, and the new metric readers on
layer contexts with and without what they read."""
from __future__ import annotations

import os

import numpy as np
import pytest

from chipbench import reference, reference_x4, run
from chipbench.loops import _search
from chipbench.tests.conftest import ROOT


@pytest.mark.parametrize("n_chunks", [16, 13])
def test_reference_x4_equals_reference(n_chunks):
    """Same blocks, same semantics: bit-identical results, a part-filled
    last segment included (one device stands in for four)."""
    import jax
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    _, cfg, _ = run.cell(bench, "deep96x4-sharded.backlog")
    cfg.update(rows=1 << 14, segment_rows=1 << 12, build_chunk_rows=1 << 10,
               precision="float32")
    seed = 3_000_000_321
    qc = _search.query_codes(cfg, seed, _search.queries(cfg, seed, 12, 0.3))
    ids = np.random.default_rng(1).integers(-1, cfg["rows"] + 3, (12, 10))
    want = reference.search_scored(cfg, seed, n_chunks, qc, ids)
    got = reference_x4.search_scored(cfg, seed, n_chunks, qc, ids,
                                     jax.devices()[:1] * 4)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


def _reader(name):
    return run.load_module(os.path.join(ROOT, "chipbench", "metrics",
                                        name + ".py")).read


def test_x4_readers():
    layer = {"trace": {"devices": 4, "idle_share": 0.1,
                       "kernels": {"scan_scored": {"events": 32,
                                                   "device_s": 12.8}},
                       "spans": {"bench.flush": [(1.7, 1.6), (1.7, 1.5)]}},
             "counters": {"batches": 2, "shard_put_bytes": 3 << 20,
                          "shard_gather_bytes": 1 << 20},
             "calls": {}, "kind": "TPU v5 lite"}
    # 12.8 s over 2 flushes and 4 chips
    assert _reader("kernel.scan_scored_ms.x4")(layer) == pytest.approx(1600)
    assert _reader("device.idle_share.x4")(layer) == pytest.approx(10.0)
    assert _reader("serve.host_ms.x4")(layer) == pytest.approx(150.0)
    assert _reader("engine.xfer_kib.x4")(layer) == 2048
    # a program without the placement counters, or no kernel: nothing
    layer["counters"] = {"batches": 2}
    layer["trace"]["kernels"] = {}
    assert _reader("engine.xfer_kib.x4")(layer) is None
    assert _reader("kernel.scan_scored_ms.x4")(layer) is None
    assert _reader("kernel.scan_scored_roofline.x4")(layer) is None
