"""The trace reduction on a small profile recorded on a TPU v5e, and the
kernels' work counts on one shape.

``data/flushes_and_ingest.xplane.pb`` holds two unscored flushes and one
scored flush over a 2^24-row corpus and one 65,536-row ``bulk_load``, cut to
the device's ``XLA Ops`` line and the harness thread (without Python
tracer events) so that it stays a few hundred KB."""
from __future__ import annotations

import glob
import os
import re

import pytest

from chipbench import readers, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    (path,) = glob.glob(os.path.join(DATA, "*.xplane.pb"))
    tr = trace.load(path)
    return tr, trace.reduce(tr)


def _window(tr):
    spans = [(s, e) for n, s, e in tr.host if n.startswith("bench.")]
    return min(s for s, _ in spans), max(e for _, e in spans)


def test_busy_is_the_union_of_device_ops(recorded):
    tr, red = recorded
    w0, w1 = _window(tr)
    (evs,) = tr.device.values()
    # sweep the sorted edges: busy where at least one op is open
    edges = sorted([(max(s, w0), 1) for _, s, e in evs if e > w0 and s < w1]
                   + [(min(e, w1), -1) for _, s, e in evs
                      if e > w0 and s < w1])
    busy, depth, last = 0.0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    assert red["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert red["window_s"] == pytest.approx((w1 - w0) / 1e9, rel=1e-12)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["idle_share"] == pytest.approx(
        1 - red["busy_s"] / red["window_s"])


def test_kernel_sums(recorded):
    tr, red = recorded
    w0, w1 = _window(tr)
    (evs,) = tr.device.values()
    seen = 0
    for name, pat in trace.kernel_patterns().items():
        mine = [(s, e) for n, s, e in evs
                if re.search(pat, n) and e > w0 and s < w1]
        assert red["kernels"][name]["events"] == len(mine)
        assert red["kernels"][name]["device_s"] == pytest.approx(
            sum(e - s for s, e in mine) / 1e9)
        seen += len(mine)
    assert seen > 0, "no kernel of kernels/ matched the recorded trace"


def test_gaps_are_idle_and_labelled(recorded):
    tr, red = recorded
    (evs,) = tr.device.values()
    assert red["idle_gaps"]
    longest = [g for _, g in red["idle_gaps"]]
    assert longest == sorted(longest, reverse=True)
    merged = trace.union([(s, e) for _, s, e in evs])
    gaps = []
    w0, w1 = _window(tr)
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            gaps.append((a, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    for (label, secs), (a, b) in zip(red["idle_gaps"], gaps):
        assert secs == pytest.approx((b - a) / 1e9)
        mid = (a + b) / 2
        assert trace.covered(merged, a, b) == 0
        assert any(n == label and s <= mid <= e for n, s, e in tr.host)


def test_work_counts_on_one_shape():
    q, n, w, k, bits, top_k = 256, 1 << 24, 16, 256, 2, 10
    ops, nbytes = readers.kernel("scan_exact").work(
        q=q, n=n, w=w, k=k, bits=bits, top_k=top_k)
    assert ops == 2 * q * n * k * 4
    assert nbytes == 4 * n * w + n // 8 + 4 * q * w + 8 * q * top_k
    p = readers.peaks("TPU v5 lite")
    # operations-bound: about 22 ms of int8 work against 1.3 ms of HBM
    assert ops / p["int8_ops"] == pytest.approx(0.0224, rel=0.01)
    assert nbytes / p["hbm_bytes_per_s"] == pytest.approx(0.00131, rel=0.01)
    ops, nbytes = readers.kernel("scan_scored").work(
        q=q, n=n, w=w, k=k, bits=bits, top_k=top_k, m=64)
    assert ops == 2 * q * n * k * 4 + 2 * q * 64 * k * 4
    assert nbytes == (4 * n * w + n // 8 + 4 * q * w + 4 * q * k * 4
                      + 8 * q * top_k)
    ops, nbytes = readers.kernel("encode").work(m=65536, d=96, k=256, w=16)
    assert ops == 2 * 65536 * 96 * 256
    assert nbytes == 4 * 65536 * 96 + 4 * 96 * 256 + 4 * 65536 * 16


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        readers.peaks("cpu")
