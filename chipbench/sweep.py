"""Find the knee of an open-loop search cell: the highest Poisson rate
whose backlog does not grow over the window.

    python3 chipbench/sweep.py --workload deep96-exact.poisson --seed 5 \
        --seconds 10 --rates 200,300,400

One set-up, then one window per rate, each with queries none of the
others sent. Per rate it prints the requests, flushes, the mean batch in
the first and second half of the window, the generator's lateness and
the latency percentiles; a backlog that grows shows as a second-half
batch above the first and a lateness that climbs with the window. The
rate found is written into the cell's traffic file by hand.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                    "src")]

from chipbench import data, run  # noqa: E402
from chipbench.loops import search_open  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    bench = run.load_json(os.path.join(os.path.dirname(HERE),
                                       "BENCHMARK.json"))
    workload, cfg, traffic = run.cell(bench, args.workload)
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        run.log("sweep: needs a TPU")
        return 3
    ctx = run.Ctx(workload, cfg, traffic, args.seed, args.seconds, False)
    counts = [data.poisson_arrivals(args.seed, r, args.seconds).size
              for r in rates]
    ctx.traffic = dict(traffic, rate_qps=sum(rates))
    state = search_open.setup(ctx)
    q, lo = state["q"], 0
    for rate, m in zip(rates, counts):
        st = {"svc": state["svc"], "q": q[lo:lo + m],
              "arrivals": data.poisson_arrivals(args.seed, rate,
                                                args.seconds)}
        lo += m
        res = search_open.window(st, ctx)
        print(json.dumps({"rate_qps": rate, **res["notes"],
                          "p95_ms": res["e2e"]["search_p95_ms"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
