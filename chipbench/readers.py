"""Shared arithmetic of the per-layer metric readers (``metrics/``).

Each reader gets the layer context of a traced run: ``trace`` (the
reduction of ``trace.reduce``), ``counters`` (the program's counters over
the traced stretch), ``calls`` (kernel name -> the shapes the harness
drove in the stretch) and ``kind`` (the device kind). A reader that
finds nothing to read returns None and its metric is left out.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(kind: str) -> dict:
    """Published peaks of one device kind; an unknown kind is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}")
    return table[kind]


def kernel(name: str):
    """The module ``kernels/<name>.py``: MATCH, PEAK and work(shape)."""
    path = os.path.join(HERE, "kernels", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_kernel_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def host_ms(layer: dict, span: str):
    """Mean host-only milliseconds of a harness span: its length minus
    the device-busy time inside it."""
    got = layer["trace"]["spans"].get(span)
    if not got:
        return None
    return 1e3 * sum(t - busy for t, busy in got) / len(got)


def per_span(layer: dict, name: str, span: str, what: str):
    """A kernel's ``events`` or device milliseconds per harness span."""
    k = layer["trace"]["kernels"].get(name, {})
    n = len(layer["trace"]["spans"].get(span, ()))
    if not k.get("events") or not n:
        return None
    return k["events"] / n if what == "events" else 1e3 * k["device_s"] / n


def kernel_ms(layer: dict, name: str):
    """Mean device milliseconds of one event of a kernel."""
    k = layer["trace"]["kernels"].get(name, {})
    if not k.get("events"):
        return None
    return 1e3 * k["device_s"] / k["events"]


def roofline(layer: dict, name: str):
    """Share (%) of a kernel's device time that its work needs at the
    chip's peaks: sum over the calls of max(operations / peak, bytes /
    bandwidth), over the summed device time of its events. None when
    the trace shows no event of it, or not one per call driven."""
    k = layer["trace"]["kernels"].get(name, {})
    calls = layer["calls"].get(name, [])
    if not k.get("events") or k["events"] != len(calls):
        return None
    mod, p = kernel(name), peaks(layer["kind"])
    need = 0.0
    for shape in calls:
        ops, nbytes = mod.work(**shape)
        need += max(ops / p[mod.PEAK], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * need / k["device_s"]

