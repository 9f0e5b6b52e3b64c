"""repro.obs — end-to-end request tracing + metrics for the whole stack.

The paper's runtime layer *monitors* dynamically changing performance
targets and hardware resources; before this package the repo could only
report aggregates (p95, energy totals).  ``repro.obs`` closes the loop
for **individual requests and decisions**: one span schema from the
cluster router down to Pallas dispatch, in both time domains.

Quick start (live)::

    from repro.obs import Tracer, MetricsRegistry, decompose_latency
    from repro.obs.export import write_chrome_trace

    tracer, metrics = Tracer(), MetricsRegistry()
    cluster = Cluster(nodes, router, tracer=tracer, metrics=metrics)
    ... serve traffic ...
    print(format_decomposition(decompose_latency(tracer)))
    write_chrome_trace(tracer, "trace.json")   # open in ui.perfetto.dev
    print(metrics.to_prometheus())

Quick start (virtual time) — the simulators accept the same objects and
emit the *same span schema* with virtual timestamps::

    report = simulate_cluster(..., tracer=Tracer(clock=lambda: 0.0))

What's inside:

* ``trace``    — :class:`Tracer`: bounded, thread-safe, tail-biased
  span buffer (always keeps the slowest K% of requests plus a seeded
  uniform sample); the fixed span vocabulary and its :data:`SCHEMA`
  (``request → route → queue → collect → stack → dispatch → device →
  complete`` plus ``arbitrate`` / ``rebalance`` / ``migrate`` /
  ``preempt`` / ``scale`` / ``health_fail`` decision spans, and one
  ``batch`` span per batch the engine dispatched);
  :func:`current_tracer`, the newest Tracer of the process.
* ``metrics``  — :class:`MetricsRegistry`: counters / gauges /
  fixed-bucket histograms with labels, Prometheus-text + JSON export,
  and the one shared nearest-rank :func:`quantile` every percentile in
  the repo routes through.
* ``analyze``  — :func:`decompose_latency`: per-class p50/p95 split
  into queue / collect / stack / dispatch / device / warming, with the
  sum-to-measured-latency invariant *asserted*, not assumed.
* ``export``   — Chrome trace-event / Perfetto JSON
  (:func:`to_chrome_trace`, :func:`write_chrome_trace`), incremental
  via the shared :class:`EventBuilder`.
* ``stream``   — :class:`TraceStreamer`: live Perfetto streaming;
  spans append to disk as requests retire (``serve.py
  --stream-trace``).
* ``health``   — the SLO watchtower: per-class multi-window burn-rate
  :class:`Alert`\\ s with regression :class:`Attribution` (which
  component regressed, ranked probable causes from chaos injections
  and decision spans) and histogram-bucket exemplars; its
  :meth:`Watchtower.pressure` signal closes the monitor→diagnose→
  actuate loop through the arbiter and rebalancer.

Design rules: stdlib-only (imported by every layer — must never cycle
or pull in jax); ``tracer=None`` everywhere means zero work on the hot
path; sims pass explicit virtual timestamps, live code lets the
injectable clock default to ``time.perf_counter``.
"""
from repro.obs.analyze import (DecompositionError, decompose_latency,
                               format_decomposition, mean_components)
from repro.obs.export import (EventBuilder, iter_trace_events,
                              to_chrome_trace, write_chrome_trace)
from repro.obs.health import (FAST, PAGE, SLOW, TICKET, Alert, Attribution,
                              BurnWindow, Cause, SLOTarget, Watchtower,
                              default_windows, format_alerts)
from repro.obs.metrics import (DEFAULT_BUCKETS_MS, Counter, Gauge,
                               Histogram, MetricsRegistry, quantile,
                               weighted_quantile)
from repro.obs.stream import TraceStreamer
from repro.obs.trace import (ARBITRATE, BATCH, BROWNOUT, CHAOS, COLLECT,
                             COMPLETE, COMPONENTS, DECISION_SPANS, DEVICE,
                             DISPATCH, HEALTH_FAIL, MIGRATE, PREEMPT, QUEUE,
                             REBALANCE, REQUEST_SPANS, ROUTE, SCALE, SCHEMA,
                             STACK, WARMING, RequestTrace, Span, Tracer,
                             current_tracer, validate_schema)

__all__ = [
    "Tracer", "Span", "RequestTrace", "SCHEMA", "COMPONENTS",
    "current_tracer", "BATCH",
    "REQUEST_SPANS", "DECISION_SPANS", "validate_schema",
    "ROUTE", "QUEUE", "COLLECT", "STACK", "DISPATCH", "DEVICE",
    "COMPLETE", "WARMING", "ARBITRATE", "REBALANCE", "MIGRATE",
    "PREEMPT", "SCALE", "HEALTH_FAIL", "CHAOS", "BROWNOUT",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "DEFAULT_BUCKETS_MS", "quantile", "weighted_quantile",
    "decompose_latency", "format_decomposition", "mean_components",
    "DecompositionError",
    "to_chrome_trace", "write_chrome_trace", "EventBuilder",
    "iter_trace_events", "TraceStreamer",
    "Watchtower", "Alert", "Attribution", "Cause", "SLOTarget",
    "BurnWindow", "default_windows", "format_alerts",
    "FAST", "SLOW", "PAGE", "TICKET",
]
