"""Unified observability: metrics, trace spans, and profiler hooks.

One process-wide layer every subsystem reports into:

    from repro import obs

    obs.counter("serve.requests").inc()
    obs.histogram("serve.latency_s", lo=1e-5, hi=100).observe(dt)
    with obs.span("serve.dispatch", key=key, bucket=bucket) as sp:
        sp.set(cache="hit")
        ...

Metrics (counters / gauges / fixed log-bucketed histograms — bounded
state, no sample lists) are ON by default; trace spans (bounded ring
buffer, parent ids, monotonic µs timestamps) are OFF by default and cost
one branch per ``span()`` call while off.  While on, every span is also a
profiler ``TraceAnnotation`` carrying its ``span_id``, so under
``jax.profiler.trace`` it lies on the device timeline's clock.
``obs.configure(metrics=..., trace=...)`` flips either plane at runtime.

Export surfaces:

  * ``obs.metrics_snapshot()`` — JSON-safe dict of every instrument;
  * ``obs.prometheus_text()`` — Prometheus text exposition
    (``lint_prometheus`` / ``python -m repro.obs`` validate it in CI);
  * ``obs.trace_events()`` / ``obs.span_tree()`` — buffered span events
    and their parent-id reconstruction.

Instrumented layers: ``serve/engine.py`` (request → dispatch → bucket →
compile spans, latency + staleness + pad-ratio histograms),
``serve/batching.py`` (bucket-cache hit/miss/eviction counters),
``stream/estimator.py`` (append/evict/flush/rebuild spans, dirty-tile and
slack-occupancy gauges), ``kernels/ops.py`` and ``kernels/spatial.py``
(a ``kernels.prune.pass`` span with one span per host step, dense launch
spans, prune visit fraction, certificate budgets, host-sync bytes) and
``kernels/autotune.py`` (resolve decisions, probe timings, occupancy
updates).  See docs/architecture.md § Observability for the span
taxonomy and metric names.
"""

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter,
    gauge,
    histogram,
    lint_prometheus,
    log_bucket_bounds,
    metrics_snapshot,
    prometheus_text,
    registry,
)
from repro.obs import state
from repro.obs.state import configure, enabled
from repro.obs.trace import (
    Span,
    clear_trace,
    current_span,
    set_trace_capacity,
    span,
    span_tree,
    trace_events,
)

__all__ = [
    "state", "configure", "enabled",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
    "counter", "gauge", "histogram",
    "log_bucket_bounds", "lint_prometheus",
    "metrics_snapshot", "prometheus_text",
    "Span", "span", "current_span",
    "trace_events", "clear_trace", "set_trace_capacity", "span_tree",
]
