"""End-to-end observability: metrics, traces, kernel stats, exporters.

Every budget the paper trades in — bits per projection vs. estimation
accuracy, HBM bytes vs. recall, coarse-pass vs. re-rank compute — is
only governable if it is *measured*; this subsystem is the measuring
layer the other six report through.

registry    — ``MetricsRegistry``: counters, gauges, fixed-log-bucket
              histograms (p50/p95/p99 without storing samples);
              process-global default + injectable instances; a disabled
              registry hands out no-op metrics
trace       — ``Tracer``/``span``: nestable spans, always written to
              the profiler's clock as ``jax.profiler`` annotations,
              recorded in Python with device-sync-correct timing while
              a ``Tracer`` is installed (``sp.sync`` =
              ``block_until_ready`` at the boundary; unsynced spans
              are *marked* async — the sync-boundary invariant) and
              exported as Chrome-trace/Perfetto JSON;
              ``install_gc_spans`` marks garbage collections
kernelstats — per-kernel-family dispatch counts + modeled FLOPs/HBM
              bytes recorded at the ``kernels/ops.py`` chokepoint; live
              roofline table against ``launch.roofline.HW``
export      — one-call JSON snapshot + Prometheus text format
quality     — online statistical health: sampled empirical collision/
              cell frequencies vs. the paper's theory curves at the MLE
              rho (z-scores, chi-square divergence) + classifier-margin
              moments, all budgeted by one sampling rate
shadow      — seeded reservoir of raw rows (capped, tombstone-aware) +
              shadow queries re-scored by exact cosine: unbiased online
              recall@k and rho-estimation error with Wilson intervals
drift       — Page-Hinkley/CUSUM detectors over the monitored series;
              registered callbacks fire on alarm (the warm-start-refit
              trigger hook); detectors report the alarm direction
events      — ``FlightRecorder`` (``obs/events.py``): always-on
              preallocated ring buffer of structured per-request
              events (op, queue/start/sync timestamps, batch, cache
              hits, generation, outcome, trace id); O(1) append cheap
              enough for the serving hot path
incident    — ``IncidentManager`` (``obs/incident.py``): on a drift
              alarm, burn-rate alarm, or endpoint error, dump a
              self-contained bundle (flight tail, retained traces,
              registry snapshot, quality state, SLO health, store
              generation) through ``repro.checkpoint``; restores to a
              readable dict
slo         — ``SloEngine`` (``obs/slo.py``): declarative per-endpoint
              ``SloSpec``s (latency/availability/quality), rolling
              multi-window error budgets from cumulative-counter
              snapshots (no stored samples), Google-SRE multi-window
              multi-burn-rate alerts on the ``DriftMonitor`` callback
              contract, and the machine-readable ``health()`` verdict
              (admission-control input)
probe       — ``CanaryProber`` (``obs/probe.py``): deterministic
              known-answer canaries drawn from the shadow reservoir,
              replayed through the real serving endpoints
              (``probe_search``/``probe_classify``) with telemetry
              segregated; verdicts feed the SLO quality budgets
resources   — ``ResourceMonitor`` (``obs/resources.py``): live-bytes
              gauges per tracked store/model, device memory watermarks,
              host RSS, and the process-wide jit-recompile counter that
              turns the never-recompile invariant into a budgeted gauge
dashboard   — zero-dependency ops view (``obs/dashboard.py``): one
              ``gather`` snapshot rendered as terminal text or a static
              self-contained HTML page (SLO budgets + burn sparklines,
              latency, resources, roofline, quality, flight tail),
              written atomically for CI artifacts

The flight layer adds retain-on-tail tracing: ``RequestTrace`` gives
every request a shallow span chain (no device barriers) and
``TailSampler`` retains full traces only for slowest-quantile /
errored / quality-flagged requests, with exemplar links
(``Histogram.exemplar``) exported on Prometheus buckets.

Instrumented layers: ``serve.ann_service`` (endpoint latencies, ticket
age and queue wait, cache + padding economics, per-request flight
events + tail sampling, a span per flush stage),
``encode.pipeline`` (chunk spans, rows/bytes),
``index.segment_log``/``index.compaction`` (append/id-map/seal spans,
churn counters, live-fraction gauge), ``ann.engine``/``index.engine``
(search span around the coarse vs. re-rank span split),
``learn.trainer`` (step time, rows/s). Overhead is
benchmarked by ``benchmarks/obs_bench.py`` (``BENCH_obs.json``); any
bench target exports a flame view via ``benchmarks/run.py --profile``;
cross-run headline numbers accumulate in ``BENCH_history.jsonl``
(``benchmarks/history.py``) and are regression-gated by
``scripts/check_perf.py``.
"""
from repro.obs.registry import (Counter, Gauge, Histogram,  # noqa: F401
                                HistogramSpec, MetricsRegistry,
                                default_registry, set_default_registry)
from repro.obs.trace import (RequestTrace, Span,  # noqa: F401
                             TailSampler, Tracer, active_tracer,
                             deep_tracing_active, install_gc_spans,
                             no_tracing, span, tracing_active)
from repro.obs.events import (EVENT_FIELDS,  # noqa: F401
                              FlightRecorder, default_flight_recorder,
                              set_flight_recorder)
from repro.obs.incident import IncidentManager  # noqa: F401
from repro.obs.kernelstats import (KernelStats,  # noqa: F401
                                   get_kernel_stats, roofline_table,
                                   set_kernel_stats)
from repro.obs.export import dump_json, snapshot, to_prometheus  # noqa: F401
from repro.obs.quality import (CollisionMonitor, MarginMonitor,  # noqa: F401
                               QualityConfig, QualityMonitors, Welford,
                               synthetic_code_pairs)
from repro.obs.shadow import (RecallMonitor, ShadowReservoir,  # noqa: F401
                              wilson_interval)
from repro.obs.drift import Cusum, DriftMonitor, PageHinkley  # noqa: F401
from repro.obs.slo import (AlertState, BurnPolicy,  # noqa: F401
                           DEFAULT_POLICIES, SloEngine, SloSpec)
from repro.obs.probe import CanaryProber, ProbeConfig  # noqa: F401
from repro.obs.resources import (ResourceMonitor,  # noqa: F401
                                 install_compile_counter, jit_compiles)
from repro.obs.dashboard import (gather, render_html,  # noqa: F401
                                 render_text, write_dashboard)
