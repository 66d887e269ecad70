"""Runs of the benchmark's cells on the CPU at a size a test can hold."""
from __future__ import annotations

import contextlib
import io
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def tiny(name: str):
    """(workload, cfg, traffic) of a cell, cut to a size a CPU test holds:
    16,384 rows in 4 segments, buckets of at most 16 queries. On the CPU
    the program projects in plain float32, so the cut cell states that
    precision in place of the chip's bfloat16 operands."""
    from chipbench import run
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    w, cfg, traffic = run.cell(bench, name)
    cfg.update(rows=1 << 14, segment_rows=1 << 12, build_chunk_rows=1 << 12,
               ingest_chunk_rows=1 << 10, precision="float32")
    cfg["service"]["buckets"] = [1, 8, 16]
    traffic.update({"search_open": dict(rate_qps=40, sample=32),
                    "search_closed": dict(clients=16, pool_flushes=4,
                                          sample=32),
                    "ingest": dict(batch_rows=2048, pool_rows=8192,
                                   swap_rows=1 << 14, sample=256),
                    }[traffic["loop"]])
    return bench, w, cfg, traffic


def run_cell(name: str, seed: int = 3_000_000_123, seconds: float = 1.0):
    """The result line of one run of a tiny cell (the TPU check
    skipped)."""
    from chipbench import run
    bench, w, cfg, traffic = tiny(name)
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0,
                                 workload=name)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert run.run(args, bench, w, cfg, traffic, chips=1) == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


@pytest.fixture
def cell():
    return run_cell
