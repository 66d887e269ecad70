"""Benchmark harness: one module per paper figure/table + system benches.

Prints ``name,us_per_call,derived`` CSV per row (scaffold contract) and
writes detailed tables to benchmarks/out/*.csv.

``--profile`` records every module's run under a ``repro.obs.Tracer``
and writes one Chrome-trace/Perfetto JSON per module next to the BENCH
files (repo root, ``TRACE_<module>.json``) — load at
https://ui.perfetto.dev for the flame view.

Every module's headline ``us_per_call`` numbers are also appended to
``BENCH_history.jsonl`` at the repo root (``benchmarks/history.py``) —
the cross-run trajectory ``scripts/check_perf.py`` regression-gates.
``--no-history`` skips the append (ad-hoc local runs).

``python benchmarks/run.py lint`` runs the docs/docstring lint
(``scripts/check_docs.py``) instead of the benchmarks.
"""
import argparse
import importlib
import os
import sys
import traceback

if __package__ in (None, ""):          # direct `python benchmarks/run.py`
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, _root)
    sys.path.insert(0, os.path.join(_root, "src"))

MODULES = [
    "benchmarks.fig01_collision",
    "benchmarks.fig02_vwq_factor",
    "benchmarks.fig03_vw_rho0",
    "benchmarks.fig04_variance_compare",
    "benchmarks.fig05_optimal_w",
    "benchmarks.fig06_p2bit",
    "benchmarks.fig07_v2bit",
    "benchmarks.fig09_onebit_ratios",
    "benchmarks.fig11_svm",
    "benchmarks.kernel_bench",
    "benchmarks.grad_compression_bench",
    "benchmarks.ann_bench",
    "benchmarks.encode_bench",
    "benchmarks.ingest_bench",
    "benchmarks.rank_bench",
    "benchmarks.learn_bench",
    "benchmarks.obs_bench",
    "benchmarks.quality_bench",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("cmd", nargs="?", default="bench",
                    choices=("bench", "lint"),
                    help="bench (default) or lint (docs/docstring checks)")
    ap.add_argument("--full", action="store_true", help="bigger sizes")
    ap.add_argument("--only", default="", help="substring filter")
    ap.add_argument("--profile", action="store_true",
                    help="trace each module; write TRACE_<module>.json "
                         "(Perfetto) next to the BENCH files")
    ap.add_argument("--no-history", action="store_true",
                    help="don't append headline numbers to "
                         "BENCH_history.jsonl")
    args = ap.parse_args()
    if args.cmd == "lint":
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, os.path.join(root, "scripts"))
        import check_docs
        raise SystemExit(check_docs.main())
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from repro.runtime import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}", file=sys.stderr)
    print("name,us_per_call,derived")
    failed = 0
    for modname in MODULES:
        if args.only and args.only not in modname:
            continue
        try:
            mod = importlib.import_module(modname)
            if args.profile:
                from repro.obs import Tracer
                short = modname.rsplit(".", 1)[-1]
                with Tracer() as tr:
                    rows = mod.run(quick=not args.full)
                path = tr.dump(os.path.join(root, f"TRACE_{short}.json"))
                print(f"# trace: {path} ({len(tr.events)} events)",
                      file=sys.stderr, flush=True)
            else:
                rows = mod.run(quick=not args.full)
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}", flush=True)
            if not args.no_history:
                from benchmarks import history as _history
                try:
                    _history.append_history(modname, rows, root,
                                            quick=not args.full)
                except OSError as e:      # read-only checkout etc.
                    print(f"# history append failed: {e}",
                          file=sys.stderr)
        except Exception:
            failed += 1
            print(f"{modname},NaN,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
