"""Self host time of the program's spans (``program.py``) on synthetic
traces and on the small profile recorded on a TPU v5e."""
from __future__ import annotations

import copy
import glob
import os

import pytest

from chipbench import program, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _synthetic():
    # device busy over [10, 20] and [40, 50]; one flush, its stages, a
    # collection, and a runtime event that is no program span
    return trace.Trace(
        device={"/device:TPU:0": [("op", 10, 20), ("op", 40, 50)]},
        host=[("bench.flush", 0, 100), ("serve.flush", 5, 95),
              ("serve.batch", 5, 30), ("DevicePutWithSharding", 31, 35),
              ("runtime.gc", 60, 70), ("bench.submit", 100, 110),
              ("serve.submit", 101, 109)])


def test_self_time_excludes_nested_spans_and_device_busy():
    got = program.reduce(_synthetic())
    # serve.flush: 90 ns less its stages [5, 30] and [60, 70] and the
    # busy [40, 50] outside them; the runtime event stays its own
    assert got["serve.flush"] == [(pytest.approx(90e-9),
                                   pytest.approx(45e-9))]
    assert got["serve.batch"] == [(pytest.approx(25e-9),
                                   pytest.approx(15e-9))]
    assert got["runtime.gc"] == [(pytest.approx(10e-9),
                                  pytest.approx(10e-9))]
    assert "bench.flush" not in got and "DevicePutWithSharding" not in got


def test_harness_time_left_and_per_flush_reading():
    tr = _synthetic()
    red = trace.reduce(tr)
    table = program.attribute(tr, red)
    assert table["per"] == "bench.flush" and table["calls"] == 1
    assert table["harness_left_ms"]["bench.flush"] == pytest.approx(10e-6)
    assert table["harness_left_ms"]["bench.submit"] == pytest.approx(2e-6)
    assert table["spans"]["serve.submit"]["self_ms"] == pytest.approx(8e-6)
    layer = {"trace": dict(red, program=program.reduce(tr))}
    assert program.ms(layer, "serve.flush", "bench.flush") == \
        pytest.approx(45e-6)
    assert program.ms(layer, "store.id_map", "bench.flush") is None
    assert program.ms({"trace": red}, "serve.flush", "bench.flush") is None


def test_program_spans_leave_the_reduction_as_it_was():
    (path,) = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    tr = trace.load(path)
    before = trace.reduce(tr)
    assert program.reduce(tr) == {}       # recorded before program spans
    flush = next(h for h in tr.host if h[0].startswith("bench.flush"))
    more = copy.deepcopy(tr)
    more.host.append(("serve.flush", flush[1] + 1, flush[2] - 1))
    after = trace.reduce(more)
    assert [g for _, g in after["idle_gaps"]] == \
        [g for _, g in before["idle_gaps"]]
    assert {k: v for k, v in after.items() if k != "idle_gaps"} == \
        {k: v for k, v in before.items() if k != "idle_gaps"}
    assert program.reduce(more)["serve.flush"]
