"""Controls of the correctness checks: stand-ins for the program in a
lower precision than the configuration states, which the cell's
comparison has to find not correct.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        [--seconds 5]

Per seed it prints the numbers the cell compares, computed for:

* ``reference``, every cell: the plain reference in the program's place,
  its projection's operands rounded to the type below the configuration's
  ``precision`` (``BELOW``: float8_e4m3fn below bfloat16, bfloat16 below
  float32);
* ``bf16_tables``, scored search: the program itself with its bfloat16
  query tables (``table_dtype="bf16"``) in place of the float32 ones the
  configuration states, over a short window at the cell's own load.

The benchmark's own runs never run this; ``tests/test_control.py``
runs it at a size a test can hold.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                    "src")]

from chipbench import data, reference, run  # noqa: E402
from chipbench.loops import _search  # noqa: E402


#: the operand type below each precision a configuration states
BELOW = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def search_reference(ctx) -> dict:
    """Numbers of search with the reference answering one step below."""
    import jax.numpy as jnp
    cfg, tr = ctx.cfg, ctx.traffic
    low = BELOW[cfg["precision"]]
    q = _search.queries(cfg, ctx.seed, tr["sample"], tr["noise"])
    r = reference.projection(data.sketch_seed(ctx.seed), cfg["d"], cfg["k"],
                             cfg["r_unit"])
    qc = reference.codes(jnp.asarray(q), r, cfg["w"], low)
    n_chunks = cfg["rows"] // cfg["build_chunk_rows"]
    none = np.zeros((q.shape[0], cfg["top_k"]), np.int32)
    if cfg["service"]["scored"]:
        v, ids, _ = reference.search_scored(cfg, ctx.seed, n_chunks, qc,
                                            none, low)
        rho = reference.rho_from_scores(v, cfg)
    else:
        v, ids, _ = reference.search_exact(cfg, ctx.seed, n_chunks, qc,
                                           none, low)
        rho = reference.rho_from_counts(v, cfg["k"], cfg["w"],
                                        cfg["estimator"]["grid"],
                                        cfg["estimator"]["rho_max"])
    return _search.check(ctx, {"queries": q, "ids": ids, "rho": rho})


def ingest_reference(ctx) -> dict:
    """Numbers of ingest with the reference storing rows one step
    below."""
    import jax.numpy as jnp
    from chipbench.loops import ingest
    cfg, tr = ctx.cfg, ctx.traffic
    rows = np.asarray(data.unit_rows(data.key(ctx.seed, data.POOL), 0,
                                     tr["sample"], cfg["d"]))
    r = reference.projection(data.sketch_seed(ctx.seed), cfg["d"], cfg["k"],
                             cfg["r_unit"])
    words = reference.pack(reference.codes(jnp.asarray(rows), r, cfg["w"],
                                           BELOW[cfg["precision"]]),
                           cfg["bits"])
    return ingest.check(ctx, {"words": words, "rows": rows,
                              "found": np.ones(rows.shape[0], bool)})


def bf16_tables(ctx) -> dict:
    """Numbers of the program's scored search with bfloat16 tables."""
    loop = run.load_module(os.path.join(HERE, "loops",
                                        ctx.traffic["loop"] + ".py"))
    cfg = ctx.cfg
    ctx.cfg = copy.deepcopy(cfg)
    ctx.cfg["service"]["table_dtype"] = "bf16"
    state = loop.setup(ctx)
    res = loop.window(state, ctx)
    out = loop.outputs(state, res, ctx)
    del state, res
    got = loop.check(ctx, out)
    ctx.cfg = cfg
    return got


def control(workload, cfg, traffic, seed: int, seconds: float) -> dict:
    """{control name: {number: (value, limit)}} of one seed."""
    ctx = run.Ctx(workload, cfg, traffic, seed, seconds, False)
    if traffic["loop"] == "ingest":
        return {"reference": ingest_reference(ctx)}
    out = {"reference": search_reference(ctx)}
    if cfg["service"]["scored"]:
        out["bf16_tables"] = bf16_tables(ctx)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    bench = run.load_json(os.path.join(os.path.dirname(HERE),
                                       "BENCHMARK.json"))
    workload, cfg, traffic = run.cell(bench, args.workload)
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("control: needs a TPU")
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        for name, got in control(workload, cfg, traffic, seed,
                                 args.seconds).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": name,
                              **{k: v for k, (v, _) in got.items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
