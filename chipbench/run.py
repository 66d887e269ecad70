"""Run one cell of BENCHMARK.json on the accelerator this process holds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell names a configuration (``configs/<name>.json``) and a traffic
mix (``traffic/<name>.json``); the mix names the loop that drives it
(``loops/<loop>.py``). A run enables the compile cache, refuses to
run without a TPU, builds the cell's state from the seed and warms its
shapes (set-up), measures for ``--seconds``, frees the program's state
and compares a seeded sample of what the window produced with the plain
reference (``reference.py``). With ``--trace 1`` a short steady stretch
of the window is profiled and the cell's per-layer metrics are read from
it by ``metrics/<name>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its
limit. The checks are also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str):
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_module(path: str):
    """Import a file of the benchmark by its path."""
    name = "chipbench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def cell(bench: dict, name: str):
    """(workload entry, configuration dict, traffic dict) of a cell."""
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; one of "
                         f"{sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return (w, load_json(os.path.join(ROOT, conf["file"])),
            load_json(os.path.join(HERE, "traffic", w["traffic"] + ".json")))


def metrics_of(bench: dict, name: str, trace: bool) -> list:
    """The cell's end-to-end metrics (trace off) or per-layer ones."""
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


class Ctx:
    """What a loop sees of the run: the cell, the seed, the window
    length, host annotations and the profiled stretch."""

    def __init__(self, workload, cfg, traffic, seed, seconds, trace):
        self.workload, self.cfg, self.traffic = workload, cfg, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.trace_dir = None
        self.traced = None        # (t0, t1) host perf_counter of the stretch
        self._t_trace = None
        self.counters = {}        # counters over the traced stretch
        self.calls = {}           # kernel name -> [shape dict] in the stretch
        self._mark = T_START

    def mark(self, phase: str) -> None:
        """Log the seconds a phase of set-up took."""
        now = time.perf_counter()
        log(f"set-up {phase}: {now - self._mark:.3f} s")
        self._mark = now

    def ann(self, name: str):
        """Host span in the profile (a no-op when not tracing)."""
        if self._t_trace is None:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def tick(self, counters=None) -> None:
        """Called by loops at each boundary of their loop (flush, call,
        step): opens the profiled stretch at the first boundary and
        closes it at the first one ``trace_seconds`` later. ``counters``
        (a dict of running totals) is snapshotted at both ends."""
        if not self.trace or self.traced is not None:
            return
        import jax
        now = time.perf_counter()
        self._last = counters or {}
        if self._t_trace is None:
            self.trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            self._c0 = dict(counters or {})
            # no Python tracer: it would slow the host path it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._t_trace = time.perf_counter()
        elif now - self._t_trace >= self.traffic["trace_seconds"]:
            self.stop()

    def stop(self) -> None:
        """Close the profiled stretch (if open) at the current boundary."""
        if self._t_trace is None:
            return
        import jax
        jax.profiler.stop_trace()
        self.traced = (self._t_trace, time.perf_counter())
        self._t_trace = None
        self.counters = {k: v - self._c0.get(k, 0)
                         for k, v in self._last.items()}

    def record(self, kernel: str, **shape) -> None:
        """One kernel call of known shape inside the profiled stretch."""
        if self._t_trace is not None:
            self.calls.setdefault(kernel, []).append(shape)


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:chips])
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips, "memory_peak_bytes": int(peak)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    workload, cfg, traffic = cell(bench, args.workload)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    from repro.runtime import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < workload["chips"]:
        log(f"needs {workload['chips']} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return 3
    log(f"{args.workload} seed {args.seed} on {devs[0].device_kind}; "
        f"compile cache {cache}")
    return run(args, bench, workload, cfg, traffic, chips=workload["chips"])


def run(args, bench, workload, cfg, traffic, chips: int) -> int:
    """Set-up, window, reference check and the result line."""
    import jax
    # programs loaded (compiled or read from the persistent cache) and
    # persistent-cache hits, per phase
    loads = {"setup": 0, "window": 0, "check": 0}
    hits = dict(loads)
    phase = ["setup"]

    def on_load(event: str, duration: float, **_):
        if "backend_compile" in event:
            loads[phase[0]] += 1

    def on_hit(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits[phase[0]] += 1
    jax.monitoring.register_event_duration_secs_listener(on_load)
    jax.monitoring.register_event_listener(on_hit)

    ctx = Ctx(workload, cfg, traffic, args.seed, args.seconds,
              bool(args.trace))
    loop = load_module(os.path.join(HERE, "loops",
                                      traffic["loop"] + ".py"))
    state = loop.setup(ctx)
    setup_s = time.perf_counter() - T_START
    phase[0] = "window"
    res = loop.window(state, ctx)
    ctx.stop()          # a window shorter than the stretch closes it
    phase[0] = "check"
    device = device_info(jax, chips)
    attempted, failed, e2e = res["attempted"], res["failed"], res["e2e"]
    log(f"set-up {setup_s:.3f} s ({loads['setup']} programs loaded, "
        f"{hits['setup']} from the cache); window {loads['window']} loaded; "
        + "; ".join(
            f"{k} {v}" for k, v in res.get("notes", {}).items()))
    outputs = loop.outputs(state, res, ctx)
    del state, res
    gc.collect()
    t_check = time.perf_counter()
    checks = loop.check(ctx, outputs)
    log(f"reference check {time.perf_counter() - t_check:.3f} s")

    out = {"correct": bool(failed == 0 and all(
        v <= lim for v, lim in checks.values())),
        "attempted": int(attempted), "failed": int(failed)}
    metrics = {}
    if args.trace:
        from chipbench import trace as tr
        red = tr.reduce(tr.load(ctx.trace_dir))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        layer = {"trace": red, "counters": ctx.counters,
                 "calls": ctx.calls, "kind": device["kind"]}
        for m in metrics_of(bench, args.workload, True):
            v = load_module(os.path.join(HERE, "metrics",
                                         m["name"] + ".py")).read(layer)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    else:
        e2e = dict(e2e, setup_s=setup_s)
        for m in metrics_of(bench, args.workload, False):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if args.trace:
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k} = {v!r} (limit {lim!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
