"""The program's own spans in a traced cell: self host time per span.

The program writes spans (``serve.*``, ``search.*``, ``engine.*``,
``encode.*``, ``store.*``, ``runtime.*``) into the profile as host
annotations on the harness thread, on the device trace's clock.
``reduce`` turns a loaded ``trace.Trace`` into, per span name inside the
traced stretch (the first to the last harness span, as ``trace.reduce``
takes it), the list of ``(seconds, self host seconds)``: self host time
is the part of the span's interval covered neither by device-busy time
nor by a program span nested in it. ``ms`` is the per-layer reading: one
span's self host milliseconds summed over the stretch, over the number
of ``per`` harness spans, or None when the span is absent.

Run as a script, it drives one cell's set-up and traced window as
``run.py --trace 1`` does (no reference check) and prints one JSON
object: every program span's count and self host ms per harness span,
the self host ms left to each harness span and to the outer program
spans, ``trace.reduce``'s idle gaps, and what a span enter and exit and
the garbage-collection hook cost on this host with no profiler running:

    python3 chipbench/program.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PREFIXES = ("serve.", "search.", "engine.", "encode.", "store.", "runtime.")


def _stretch(tr):
    spans = [(s, e) for n, s, e in tr.host if n.startswith("bench.")]
    if not spans:
        raise ValueError("trace holds no harness span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _busy(tr, w0, w1):
    """Per device: merged busy intervals clipped to the stretch."""
    from chipbench import trace
    return [trace.union([(max(s, w0), min(e, w1)) for _, s, e in evs
                         if e > w0 and s < w1])
            for evs in tr.device.values()]


def self_times(spans, children, busy):
    """[(name, seconds, self host seconds)] of ``spans`` [(n, s, e)]:
    each span's length less the part of it that device-busy intervals
    (``busy``: merged intervals per device, averaged over devices) or
    the ``children`` events [(n, s, e)] nested in it cover."""
    from chipbench import trace
    kids = sorted((s, e) for _, s, e in children)
    kid_starts = [s for s, _ in kids]
    out = []
    for n, s, e in spans:
        lo = bisect.bisect_left(kid_starts, s)
        hi = bisect.bisect_right(kid_starts, e)
        inner = [(a, b) for a, b in kids[lo:hi]
                 if b <= e and (a, b) != (s, e)]
        left = 0.0
        for dev in busy or [[]]:
            merged = trace.union(inner + [
                (max(a, s), min(b, e)) for a, b in dev if b > s and a < e])
            left += (e - s) - sum(b - a for a, b in merged)
        out.append((n, (e - s) / 1e9, left / len(busy or [[]]) / 1e9))
    return out


def _program(tr, w0, w1):
    return [h for h in tr.host if h[0].startswith(PREFIXES)
            and h[1] >= w0 and h[2] <= w1]


def reduce(tr) -> dict:
    """Span name -> [(seconds, self host seconds)] of the program spans
    of the harness thread that fall inside the traced stretch."""
    w0, w1 = _stretch(tr)
    mine = _program(tr, w0, w1)
    out = {}
    for n, t, own in self_times(mine, mine, _busy(tr, w0, w1)):
        out.setdefault(n, []).append((t, own))
    return out


def ms(layer: dict, span: str, per: str):
    """One program span's self host milliseconds over the stretch, per
    ``per`` harness span; None when either is absent."""
    got = layer["trace"].get("program", {}).get(span)
    n = len(layer["trace"]["spans"].get(per, ()))
    if not got or not n:
        return None
    return 1e3 * sum(own for _, own in got) / n


def costs(n: int = 200_000) -> dict:
    """Nanoseconds of a span's enter and exit with no tracer and no
    profiler, with and without annotation metadata, and what the
    garbage-collection hook adds to one young collection."""
    import gc
    from repro.obs import install_gc_spans, span

    def each(f, reps):
        t = time.perf_counter()
        for _ in range(reps):
            f()
        return (time.perf_counter() - t) / reps * 1e9

    def plain():
        with span("serve.batch"):
            pass

    def meta():
        with span("serve.flush", meta=True, pending=256, trace_id=1):
            pass
    out = {"span_ns": each(plain, n), "span_meta_ns": each(meta, n)}
    enabled = gc.isenabled()
    gc.disable()
    try:
        hooks = gc.callbacks[:]
        gc.callbacks[:] = [cb for cb in hooks
                           if type(cb).__name__ != "_GcSpans"]
        bare = each(lambda: gc.collect(0), n // 20)
        gc.callbacks[:] = hooks
        install_gc_spans()
        out["gc_hook_ns"] = each(lambda: gc.collect(0), n // 20) - bare
    finally:
        if enabled:
            gc.enable()
    return out


def attribute(tr, red: dict) -> dict:
    """The attribution of one trace, per call of the cell's harness span
    (``bench.flush``, else ``bench.bulk_load``): for each program span,
    its events, milliseconds and self host milliseconds (``ms``); and
    the self host milliseconds left to each harness span name, per span
    of that name."""
    from chipbench import trace
    per = "bench.flush" if "bench.flush" in red["spans"] \
        else "bench.bulk_load"
    calls = len(red["spans"][per])
    layer = {"trace": dict(red, program=reduce(tr))}
    spans = {n: {"events": len(v) / calls,
                 "ms": 1e3 * sum(t for t, _ in v) / calls,
                 "self_ms": ms(layer, n, per)}
             for n, v in sorted(layer["trace"]["program"].items())}
    w0, w1 = _stretch(tr)
    harness = [h for h in tr.host if h[0].startswith(trace.SPAN_PREFIX)]
    left = {}
    for n, _, own in self_times(harness, _program(tr, w0, w1),
                                _busy(tr, w0, w1)):
        left.setdefault(n, []).append(own)
    return {"per": per, "calls": calls, "spans": spans,
            "harness_left_ms": {n: 1e3 * sum(v) / len(v)
                                for n, v in left.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import run, trace
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    workload, cfg, traffic = run.cell(bench, args.workload)
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        run.log(f"needs a TPU; JAX found {devs[0].platform}")
        return 3
    ctx = run.Ctx(workload, cfg, traffic, args.seed, args.seconds, True)
    loop = run.load_module(os.path.join(HERE, "loops",
                                        traffic["loop"] + ".py"))
    res = loop.window(loop.setup(ctx), ctx)
    ctx.stop()
    tr = trace.load(ctx.trace_dir)
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    red = trace.reduce(tr)
    table = attribute(tr, red)
    out = {"workload": args.workload, "seed": args.seed,
           "device": devs[0].device_kind, "notes": res.get("notes", {}),
           "counters": ctx.counters, "window_s": red["window_s"],
           "busy_s": red["busy_s"],
           "host_ms": {n: 1e3 * sum(t - b for t, b in v) / len(v)
                       for n, v in red["spans"].items()},
           **table, "idle_gaps": red["idle_gaps"], "costs": costs()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
