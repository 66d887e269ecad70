"""Reduction of a profiler trace (xplane) to the numbers readers use.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote; ``reduce``
turns it into:

* ``window_s``: the traced stretch, from the first to the last harness
  span (``bench.*`` host annotations);
* ``busy_s``: the union of device-op intervals inside it, averaged over
  the devices traced; ``idle_share`` = 1 - busy / window;
* ``kernels``: per kernel of ``kernels/<name>.py``, the events whose name
  its ``MATCH`` pattern finds, their count and summed device seconds;
* ``spans``: per harness span name, each span's (seconds, device-busy
  seconds inside it);
* ``device_ops``: the ten device operations that took most time;
* ``idle_gaps``: the ten longest gaps between device operations, each
  named by the innermost host event open at its middle.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclass
class Trace:
    """Events as (name, start_ns, end_ns): device ops per device plane,
    and the host events of the thread that ran the harness."""
    device: dict = field(default_factory=dict)   # plane -> [(n, s, e)]
    host: list = field(default_factory=list)     # [(n, s, e)]


def load(path: str) -> Trace:
    """Read the one ``.xplane.pb`` under ``path`` (a file or the
    directory ``jax.profiler.start_trace`` was given)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise ValueError(f"{len(found)} xplane files under {path}")
        path = found[0]
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            tr.device[plane.name] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            # the thread that ran the harness: its spans and what the
            # program did under them
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if any(n.startswith(SPAN_PREFIX) for n, _, _ in evs):
                    tr.host.extend(evs)
    return tr


def short(name: str) -> str:
    """An HLO op's event name without layouts, cut to 120 characters."""
    return re.sub(r"\{[^{}]*\}", "", name)[:120]


def union(intervals):
    """Merged, sorted, disjoint intervals of [(start, end)]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def covered(merged, s: float, e: float) -> float:
    """Length of [s, e] covered by merged intervals."""
    return sum(max(0.0, min(e, b) - max(s, a)) for a, b in merged)


def kernel_patterns() -> dict:
    """Kernel name -> compiled MATCH pattern of ``kernels/<name>.py``."""
    from chipbench import readers
    return {name: re.compile(readers.kernel(name).MATCH)
            for name in sorted(os.path.basename(p)[:-3] for p in glob.glob(
                os.path.join(HERE, "kernels", "*.py")))}


def reduce(tr: Trace) -> dict:
    """Per-layer raw numbers of the traced stretch (see module doc); the
    stretch is taken from the harness spans, on the trace's own clock."""
    spans = [h for h in tr.host if h[0].startswith(SPAN_PREFIX)]
    if not spans or not tr.device:
        raise ValueError("trace holds no harness span or no device plane")
    w0, w1 = min(s for _, s, _ in spans), max(e for _, _, e in spans)
    n_dev = len(tr.device)
    merged, ops = {}, {}
    for plane, evs in tr.device.items():
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in evs
                  if e > w0 and s < w1]
        merged[plane] = union([(s, e) for _, s, e in inside])
        for n, s, e in inside:
            ops[short(n)] = ops.get(short(n), 0) + (e - s)
    busy = sum(covered(m, w0, w1) for m in merged.values()) / n_dev
    kernels = {}
    for name, pat in kernel_patterns().items():
        evs = [(s, e) for plane in tr.device.values() for n, s, e in plane
               if pat.search(n) and e > w0 and s < w1]
        kernels[name] = {"events": len(evs),
                         "device_s": sum(e - s for s, e in evs) / 1e9}
    per_span = {}
    for n, s, e in spans:
        inside = sum(covered(m, s, e) for m in merged.values()) / n_dev
        per_span.setdefault(n, []).append(((e - s) / 1e9, inside / 1e9))
    gaps = []
    for m in merged.values():
        edges = [w0] + [x for iv in m for x in iv] + [w1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy / 1e9,
        "idle_share": 1.0 - busy / (w1 - w0),
        "devices": n_dev,
        "kernels": kernels,
        "spans": per_span,
        "device_ops": [[n, t / 1e9 / n_dev] for n, t in sorted(
            ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[_label(tr.host, (a + b) / 2), (b - a) / 1e9]
                      for a, b in gaps],
    }


def _label(host, t: float) -> str:
    """The innermost host event open at time t (or 'no host event')."""
    open_ = [(e - s, n) for n, s, e in host if s <= t <= e]
    return min(open_)[1] if open_ else "no host event"
