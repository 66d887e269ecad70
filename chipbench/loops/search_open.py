"""Open-loop served search: independent users, arrivals on a schedule.

Traffic keys: ``rate_qps`` (mean rate of Poisson arrivals, one query
each, a noisy copy of a corpus row; every query is distinct), ``noise``,
``sample`` (queries the reference checks), ``trace_seconds``.

The harness loop is the server: it submits every request that is due,
flushes, and sleeps until the next one is due. A request is timed from
when it was due to when its flush returned, so a stall counts against
every request it delays. ``search_p95_ms`` is the 95th percentile of
that latency over every request of the window; the window holds
``rate_qps * seconds`` arrivals and closes when the last is answered.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import data
from chipbench.loops import _search


def setup(ctx):
    tr = ctx.traffic
    arrivals = data.poisson_arrivals(ctx.seed, tr["rate_qps"], ctx.seconds)
    m = arrivals.size
    svc, q = _search.build(ctx, m + 256)
    q, warm = q[:m], q[m:]
    d = ctx.cfg["d"]
    svc.warmup(d)
    ctx.mark("bucket programs")
    top = svc.cfg.buckets[-1]
    _search.warm_sizes(ctx, svc, range(1, top + 1))
    ctx.mark("batch sizes")
    for b in svc.cfg.buckets:           # the served path at every bucket
        for row in warm[:b]:
            svc.submit(row)
        svc.flush()
    ctx.mark("served path")
    return {"svc": svc, "q": q, "arrivals": arrivals}


def window(state, ctx):
    svc, q, arr = state["svc"], state["q"], state["arrivals"]
    m = arr.size
    tickets = np.empty(m, np.int64)
    sent = np.empty(m)
    done = np.full(m, np.nan)
    results = {}
    keep = set(_search.sample(ctx.seed, m, ctx.traffic["sample"]).tolist())
    by_ticket = {}
    batches = []
    i = 0
    t0 = time.perf_counter()
    while i < m or svc.pending():
        now = time.perf_counter() - t0
        with ctx.ann("bench.submit"):
            while i < m and arr[i] <= now:
                tickets[i] = svc.submit(q[i])
                by_ticket[int(tickets[i])] = i
                sent[i] = time.perf_counter() - t0
                i += 1
        n = svc.pending()
        if n:
            _search.flush_calls(ctx, svc, n)
            with ctx.ann("bench.flush"):
                out = svc.flush()
            t = time.perf_counter() - t0
            for tk, (ids, rho) in out.items():
                j = by_ticket.pop(tk, None)
                if j is None:
                    continue
                done[j] = t
                if j in keep:
                    results[j] = (ids, rho)
            batches.append((now, n))
            ctx.tick(dict(svc.stats))
        elif i < m:
            with ctx.ann("bench.wait"):
                time.sleep(max(0.0, arr[i] - (time.perf_counter() - t0)))
    lat = np.where(np.isnan(done), np.inf, done - arr) * 1e3
    late = (sent - arr) * 1e3

    def pct(q):     # nearest rank: an unanswered request reads inf
        return float(np.percentile(lat, q, method="higher"))
    sizes = np.array([b for _, b in batches])
    half = len(batches) // 2
    notes = {
        "requests": m, "flushes": len(batches),
        "latency_ms p50/p95/max": [pct(50), pct(95), float(lat.max())],
        "generator late_ms p95/max": [float(np.percentile(late, 95)),
                                      float(late.max())],
        "batch mean first/second half": [
            float(sizes[:half].mean()) if half else 0.0,
            float(sizes[half:].mean())],
        "window_s": float(np.nanmax(done)),
    }
    order = sorted(results)
    return {"attempted": m, "failed": int(np.isnan(done).sum()),
            "e2e": {"search_p95_ms": pct(95)},
            "notes": notes,
            "sampled": {"queries": q[order],
                        "ids": np.stack([results[j][0] for j in order]),
                        "rho": np.stack([results[j][1] for j in order])}}


def outputs(state, res, ctx):
    return res["sampled"]


def check(ctx, out):
    return _search.check(ctx, out)
