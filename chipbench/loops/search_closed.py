"""Closed-loop served search: a fixed set of clients, one query each
outstanding, each sending its next query when its answer returns.

Traffic keys: ``clients``, ``pool_flushes`` (distinct queries made in
set-up: ``clients * pool_flushes``, sent in turn; the result cache holds
far fewer, so none repeats while it could hit), ``noise``, ``sample``,
``trace_seconds``.

Every flush carries one query per client. ``search_qps`` is the queries
answered over the whole window, which closes at the first flush boundary
at or after ``--seconds``.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench.loops import _search


def setup(ctx):
    tr = ctx.traffic
    c = tr["clients"]
    svc, q = _search.build(ctx, c * (tr["pool_flushes"] + 1))
    for row in q[-c:]:                  # the one bucket the window uses
        svc.submit(row)
    svc.flush()
    ctx.mark("served path")
    return {"svc": svc, "q": q[:-c]}


def window(state, ctx):
    svc, q = state["svc"], state["q"]
    c = ctx.traffic["clients"]
    sent, answers = 0, []
    t0 = time.perf_counter()
    while True:
        with ctx.ann("bench.submit"):
            tickets = [svc.submit(q[(sent + j) % len(q)]) for j in range(c)]
        _search.flush_calls(ctx, svc, c)
        with ctx.ann("bench.flush"):
            out = svc.flush()
        answers.append([out.get(t) for t in tickets])
        sent += c
        t = time.perf_counter() - t0
        ctx.tick(dict(svc.stats))
        if t >= ctx.seconds:
            break
    flat = [a for batch in answers for a in batch]
    keep = np.array([j for j in _search.sample(ctx.seed, sent,
                                               ctx.traffic["sample"])
                     if flat[j] is not None], np.int64)
    return {"attempted": sent,
            "failed": sum(a is None for a in flat),
            "e2e": {"search_qps": sent / t},
            "notes": {"flushes": len(answers), "window_s": t,
                      "flush_s mean": t / len(answers)},
            "sampled": {"queries": q[keep % len(q)],
                        "ids": np.stack([flat[j][0] for j in keep]),
                        "rho": np.stack([flat[j][1] for j in keep])}}


def outputs(state, res, ctx):
    return res["sampled"]


def check(ctx, out):
    return _search.check(ctx, out)
