"""Nestable tracing spans with device-sync-correct timing.

The timing trap this module exists to close: jax dispatch is async, so
``t1 - t0`` around a device call measures *submission*, not execution —
exactly the bug that produced a negative (clamped-to-zero) re-rank
overhead in ``BENCH_rank.json``. A span therefore closes in one of two
explicitly-labelled states:

* **device-synced** — the code inside called ``sp.sync(value)`` (a
  ``jax.block_until_ready`` that returns its argument), so the span's
  duration covers the device work that produced ``value``;
* **async** — no sync happened before close (either ``sync=False`` was
  requested, or the caller simply never synced). The span is marked
  ``"sync": "async"`` in the trace.

That labelling is the sync-boundary invariant documented in
``docs/ARCHITECTURE.md``: a span that closes without a device sync is
*always* marked async — there is no state in which an unsynced duration
masquerades as an execution time.

One span mechanism, always on the device trace's clock: every
``span(...)`` opens a ``jax.profiler.TraceAnnotation`` of its name, so
whenever ``jax.profiler`` is tracing, the span lands in the profile's
host plane beside the device ops it launched (about 0.6 us per span on
a TPU v5e host with no profiler running). Recording into Python is
opt-in on top of that: ``with Tracer() as tr`` installs a tracer, and
while none is installed ``span(...)`` returns an annotation-only span
whose ``sync`` is a passthrough, so untraced runs get no device
barrier. Finished
traces export to Chrome-trace / Perfetto JSON (``Tracer.dump``): load
the file in ``chrome://tracing`` or https://ui.perfetto.dev to see a
whole ingest→search→compact run as a flame view. Span attributes go
to the tracer's ``args``; an outer span that should carry them into
the profile too (``serve.flush``: its ``trace_id`` joins the flight
events) passes ``meta=True``.

``install_gc_spans`` adds a ``gc.callbacks`` hook that brackets every
Python garbage collection in a ``runtime.gc`` annotation, so a
collection's pause shows on the same clock as the spans it interrupts.

Two tracer depths exist. A plain ``Tracer`` is **deep**: ``sp.sync``
really blocks, so durations are execution-true — the profiling mode of
``benchmarks/run.py --profile``. A ``RequestTrace`` (installed per
request by ``TailSampler``) is **shallow**: spans are recorded with
submission timings and ``sp.sync`` never blocks, so the always-on
request span chains add no device barriers to the serving pipeline.
Shallow spans are honestly labelled ``"sync": "async"`` — the
sync-boundary invariant is never weakened, only the *blocking* is
skipped. Code that must behave differently under real profiling (the
engines' device-synced chunk paths) checks ``deep_tracing_active()``,
not ``tracing_active()``.

``TailSampler`` implements the retain-on-tail policy: every request is
*recorded* (cheap shallow chain), but the full trace is *retained* only
when the request lands in the slowest-quantile tail of past requests,
raises, or is flagged by a quality monitor. Retention decisions use
only (a) past observations and (b) one seeded RNG, so a replayed
workload retains the same trace ids.
"""
from __future__ import annotations

import gc
import json
import threading
import time
from collections import OrderedDict

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from .registry import Histogram, HistogramSpec, default_registry

__all__ = ["Span", "Tracer", "RequestTrace", "TailSampler", "span",
           "tracing_active", "deep_tracing_active", "active_tracer",
           "no_tracing", "install_gc_spans"]

_ACTIVE: "Tracer | None" = None


def tracing_active() -> bool:
    """Whether a tracer is currently installed (spans are recording)."""
    return _ACTIVE is not None


def deep_tracing_active() -> bool:
    """Whether a *deep* tracer is installed — one whose ``sp.sync``
    really blocks. Engines use this to pick their device-synced
    per-chunk paths; a shallow ``RequestTrace`` never triggers them."""
    return _ACTIVE is not None and _ACTIVE.deep


def active_tracer() -> "Tracer | None":
    """The installed tracer, or None."""
    return _ACTIVE


class Span:
    """One live span; use via ``with span("name") as sp``.

    Call ``sp.sync(value)`` on the device results produced inside the
    span — it blocks until they are ready (so the closing timestamp is
    execution-true) and returns them. Extra attributes land in the
    Chrome-trace ``args`` via ``sp.set(key=...)`` or the ``span(...)``
    kwargs. The span also opens its profiler annotation.
    """

    __slots__ = ("tracer", "name", "args", "sync_wanted", "t0", "_synced",
                 "_ann")

    def __init__(self, tracer: "Tracer", name: str, sync_wanted: bool,
                 args: dict, ann: TraceAnnotation):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.sync_wanted = sync_wanted
        self.t0 = 0.0
        self._synced = False
        self._ann = ann

    def sync(self, value):
        """Block until ``value`` (any pytree of arrays) is ready; marks
        the span device-synced and returns ``value``. Under a shallow
        tracer (``RequestTrace``) this is a passthrough — no block, no
        synced mark — so always-on request tracing never serialises the
        pipeline; the span stays labelled async, which is the truth."""
        if self.tracer.deep:
            jax.block_until_ready(value)
            self._synced = True
        return value

    def set(self, **attrs):
        """Attach attributes to the span's trace ``args``."""
        self.args.update(attrs)

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        self.tracer._push(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        self.args["sync"] = "device" if self._synced else "async"
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        self.tracer._pop(self, t1)
        self._ann.__exit__(exc_type, exc, tb)
        return False                      # never swallow exceptions


class _AnnotationSpan(TraceAnnotation):
    """The span returned while no tracer is installed: the profiler
    annotation alone. ``sync`` is a passthrough (no block) and ``set``
    records nothing, so untraced runs get no device barrier and no
    Python-side bookkeeping."""

    def sync(self, value):
        """Passthrough: no block, no recording."""
        return value

    def set(self, **attrs):
        """No-op: attributes are recorded only by an installed tracer."""


def span(name: str, sync: bool = True, meta: bool = False, **attrs):
    """Open a span: always a profiler annotation called ``name``, and a
    recorded span on the installed tracer when there is one.

    ``sync=True`` declares the span *should* close device-synced — the
    body is expected to route its device results through ``sp.sync``;
    if it never does, the span is recorded but labelled async.
    ``sync=False`` declares an async span up front (e.g. enqueue-only
    work). ``meta=True`` also writes ``attrs`` into the annotation, as
    event metadata of the profile (for outer spans only: it nearly
    doubles the span's cost). Returns a context manager either way.
    """
    tr = _ACTIVE
    if tr is None:
        return (_AnnotationSpan(name, **attrs) if meta
                else _AnnotationSpan(name))
    ann = TraceAnnotation(name, **attrs) if meta else TraceAnnotation(name)
    return Span(tr, name, sync, dict(attrs), ann)


class _GcSpans:
    """``gc.callbacks`` hook: a ``runtime.gc`` annotation from each
    collection's start to its stop, its generation as metadata.
    Collections never nest and start and stop on one thread, so one
    open annotation is all the state there is."""

    __slots__ = ("_ann",)

    def __init__(self):
        self._ann = None

    def __call__(self, phase: str, info: dict):
        if phase == "start":
            self._ann = TraceAnnotation("runtime.gc",
                                        generation=info["generation"])
            self._ann.__enter__()
        elif self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None


_GC_SPANS = _GcSpans()


def install_gc_spans() -> None:
    """Bracket every garbage collection of this process in a
    ``runtime.gc`` profiler annotation (idempotent: the hook is
    installed once, however often this is called)."""
    if _GC_SPANS not in gc.callbacks:
        gc.callbacks.append(_GC_SPANS)


class _NoTracing:
    """Suspends the installed tracer for the duration of a block."""

    __slots__ = ("_prev",)

    def __enter__(self):
        global _ACTIVE
        self._prev = _ACTIVE
        _ACTIVE = None
        return self

    def __exit__(self, exc_type, exc, tb):
        global _ACTIVE
        _ACTIVE = self._prev
        return False


def no_tracing() -> _NoTracing:
    """Context manager suspending span recording inside its block —
    for sections too hot to trace, or for measuring the no-tracer span
    cost itself while a tracer happens to be installed. The spans'
    profiler annotations are not suspended."""
    return _NoTracing()


class Tracer:
    """Span collector + Chrome-trace exporter; ``with Tracer() as tr``
    installs it globally for the duration of the block.

    Spans nest per-thread (a stack keyed on thread id); nesting in the
    exported trace is carried by timestamp containment on one track,
    which is exactly how chrome://tracing / Perfetto build flames.
    """

    #: deep tracers make ``sp.sync`` really block (execution-true
    #: durations); ``RequestTrace`` overrides this to False per instance.
    deep = True

    def __init__(self):
        self.events: list[dict] = []      # finished spans, close order
        self._stacks: dict[int, list] = {}
        self._tids: dict[int, int] = {}
        self._t0 = time.perf_counter()
        self._prev = None

    # -- span bookkeeping (called by Span) -----------------------------------
    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            tid = self._tids[ident] = len(self._tids)
        return tid

    def _push(self, sp: Span):
        self._stacks.setdefault(threading.get_ident(), []).append(sp)

    def _pop(self, sp: Span, t1: float):
        stack = self._stacks[threading.get_ident()]
        # exception-safe: unwind past any inner spans abandoned by a raise
        while stack and stack[-1] is not sp:
            stack.pop()
        if stack:
            stack.pop()
        self.events.append({
            "name": sp.name, "ts": sp.t0 - self._t0,
            "dur": t1 - sp.t0, "tid": self._tid(), "depth": len(stack),
            "args": sp.args})

    def depth(self) -> int:
        """Current nesting depth on the calling thread."""
        return len(self._stacks.get(threading.get_ident(), ()))

    # -- install / uninstall -------------------------------------------------
    def __enter__(self) -> "Tracer":
        global _ACTIVE
        self._prev = _ACTIVE
        _ACTIVE = self
        return self

    def __exit__(self, exc_type, exc, tb):
        global _ACTIVE
        _ACTIVE = self._prev
        return False

    # -- queries -------------------------------------------------------------
    def durations(self, name: str) -> list:
        """Seconds of every finished span called ``name``."""
        return [e["dur"] for e in self.events if e["name"] == name]

    def total(self, name: str) -> float:
        """Summed seconds across every finished span called ``name``."""
        return sum(self.durations(name))

    # -- export --------------------------------------------------------------
    def to_chrome(self) -> dict:
        """Chrome-trace JSON object (``traceEvents`` complete events,
        timestamps in microseconds) — loadable by chrome://tracing and
        Perfetto."""
        events = [{
            "name": e["name"], "ph": "X", "pid": 0, "tid": e["tid"],
            "ts": round(e["ts"] * 1e6, 3),
            "dur": round(e["dur"] * 1e6, 3),
            "args": e["args"],
        } for e in self.events]
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def dump(self, path: str) -> str:
        """Write the Chrome-trace JSON to ``path``; returns ``path``."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path


class RequestTrace(Tracer):
    """Lightweight per-request span chain — the always-on tracer.

    Shallow by default: spans record submission timings, ``sp.sync``
    never blocks, and every span's ``args`` carry the request's
    ``trace_id`` (the id exported as an exemplar link and stamped on
    flight-recorder events). When an *outer deep* tracer is already
    installed (``run.py --profile``), the request trace inherits
    ``deep=True`` and forwards its finished spans — rebased onto the
    outer clock — so profiling sees everything and loses nothing.
    """

    def __init__(self, trace_id: int, outer: "Tracer | None" = None):
        super().__init__()
        self.trace_id = trace_id
        self._outer = outer
        self.deep = outer.deep if outer is not None else False

    def _pop(self, sp: Span, t1: float):
        sp.args["trace_id"] = self.trace_id
        super()._pop(sp, t1)
        if self._outer is not None:
            e = dict(self.events[-1])
            e["ts"] += self._t0 - self._outer._t0
            self._outer.events.append(e)


class _Request:
    """Handle for one sampled request (yielded by ``TailSampler.request``).

    Inside the block a ``RequestTrace`` is installed, so every
    ``span(...)`` down the call stack joins this request's chain. Call
    ``set_key`` to choose the tail-ranking key (e.g. deadline-relative
    lateness; defaults to wall duration), ``flag(reason)`` to force
    retention (quality monitors do). After the block, ``retained`` /
    ``reason`` say what the sampler decided.
    """

    __slots__ = ("sampler", "op", "attrs", "trace", "trace_id", "key",
                 "_flags", "_t0", "retained", "reason")

    def __init__(self, sampler: "TailSampler", op: str, attrs: dict):
        self.sampler = sampler
        self.op = op
        self.attrs = attrs
        self.trace_id = sampler._next_id()
        self.key = None
        self._flags = []
        self.retained = False
        self.reason = ""

    def set_key(self, key: float):
        """Set the tail-ranking key (higher = more worth retaining)."""
        self.key = float(key)

    def flag(self, reason: str):
        """Force retention of this request's trace (e.g. a quality
        monitor fired mid-request)."""
        self._flags.append(str(reason))

    def __enter__(self) -> "_Request":
        outer = _ACTIVE
        self.trace = RequestTrace(
            self.trace_id, outer if outer is not None and outer.deep
            else None)
        self.trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self._t0
        self.trace.__exit__(exc_type, exc, tb)
        self.sampler._finish(self, dur, exc_type)
        return False                      # never swallow exceptions


class _NullRequest:
    """Shared no-op request handle (disabled ``TailSampler``)."""

    __slots__ = ()
    trace_id = 0
    retained = False
    reason = ""

    def set_key(self, key):
        """No-op."""

    def flag(self, reason):
        """No-op."""

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_REQUEST = _NullRequest()


class TailSampler:
    """Tail-based trace retention: record everything, keep the tail.

    Every ``request(...)`` gets a shallow ``RequestTrace`` (cheap, no
    device barriers). On close, the trace is **retained** only when:

    * ``slow`` — its key lands above the ``quantile`` of all *past*
      request keys (a reservoir of the slowest tail; keys default to
      wall duration, the serving layer uses deadline-relative lateness);
    * ``error`` — the block raised;
    * ``flagged`` — something called ``handle.flag(...)`` (quality
      monitors wire their drift callbacks here);
    * ``sampled`` — a seeded coin (``sample_rate``) kept it as a
      baseline exemplar of normal traffic.

    Determinism: the slow threshold is computed from past observations
    *before* the new key is recorded, trace ids are a per-sampler
    monotone counter, and the coin is a seeded ``default_rng`` — a
    replayed workload makes identical retention decisions
    (``tests/test_flight.py`` pins this). Retained traces live in an
    LRU capped at ``max_retained``; ``flight.requests`` /
    ``flight.retained`` counters land in the registry.
    """

    def __init__(self, quantile: float = 0.95, max_retained: int = 32,
                 min_count: int = 20, sample_rate: float = 0.0,
                 seed: int = 0, registry=None, enabled: bool = True):
        if not 0.0 < quantile < 1.0:
            raise ValueError(f"quantile must be in (0,1), got {quantile}")
        self.enabled = enabled
        self.quantile = float(quantile)
        self.max_retained = int(max_retained)
        self.min_count = int(min_count)
        self.sample_rate = float(sample_rate)
        self._rng = np.random.default_rng(seed)
        # past request keys; keys can be negative (early vs deadline) —
        # those clamp into bucket 0, which only sharpens the tail.
        self._keys = Histogram("flight.request_key",
                               HistogramSpec(lo=1e-6, hi=1e4))
        self.retained: "OrderedDict[int, dict]" = OrderedDict()
        self._id = 0
        reg = registry if registry is not None else default_registry()
        self._c_requests = reg.counter("flight.requests")
        self._c_retained = reg.counter("flight.retained")

    def _next_id(self) -> int:
        self._id += 1
        return self._id

    def request(self, op: str, **attrs):
        """Open a sampled request block: ``with sampler.request("search")
        as rq:``. See ``_Request`` for the handle API. A sampler built
        with ``enabled=False`` returns a shared no-op handle (no
        request trace, no retention, no counters) — the off switch the
        flight-overhead bench measures against."""
        if not self.enabled:
            return _NULL_REQUEST
        return _Request(self, op, dict(attrs))

    def threshold(self) -> float:
        """Current slow-tail key threshold (inf during warmup)."""
        if self._keys.count < self.min_count:
            return float("inf")
        return self._keys.percentile(self.quantile)

    def _finish(self, rq: _Request, dur: float, exc_type):
        key = rq.key if rq.key is not None else dur
        if exc_type is not None:
            reason = "error"
            rq.attrs["error"] = exc_type.__name__
        elif rq._flags:
            reason = "flagged:" + ",".join(rq._flags)
        elif key >= self.threshold():
            reason = "slow"
        elif self.sample_rate > 0.0 and \
                self._rng.random() < self.sample_rate:
            reason = "sampled"
        else:
            reason = ""
        self._keys.observe(key)           # after the decision: past-only
        self._c_requests.inc()
        if reason:
            self._retain(rq, reason, key, dur)
        rq.retained = bool(reason)
        rq.reason = reason

    def _retain(self, rq: _Request, reason: str, key: float, dur: float):
        self.retained[rq.trace_id] = {
            "trace_id": rq.trace_id, "op": rq.op, "reason": reason,
            "key": key, "dur": dur, "attrs": rq.attrs,
            "events": rq.trace.events}
        self._c_retained.inc()
        while len(self.retained) > self.max_retained:
            self.retained.popitem(last=False)

    def retained_traces(self) -> list:
        """Retained trace records, oldest first — what an incident
        bundle captures and ``obs.export`` links exemplars against."""
        return list(self.retained.values())
