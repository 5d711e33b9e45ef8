"""Unified observability layer: structured tracing, per-request timelines,
an autotune audit trail, and a chaos flight recorder.

The repo's three measurement pillars before this package were offline: the
BENCH json (perf), the fault-injection harnesses (robustness), and
``ServeMetrics`` (serving-only aggregates). None of them could attribute a
slow request to queue wait vs pack vs kernel wall, say *why* the autotuner
picked ``pallas_gemm`` over ``pallas_fused`` for a layer, or produce a
post-mortem artifact when a chaos run kills a replica. ``repro.obs`` is
that missing leg — production telemetry in the GANAX / HUGE^2 sense
(unit-level utilization, per-stage decomposition), built on the stdlib,
numpy and JAX's profiler annotations only, and **disabled by default**:

* :mod:`repro.obs.trace` — process-global :class:`~repro.obs.trace.Tracer`
  with nestable spans, monotonic-clock timestamps, counters/gauges/
  observation series, and a no-op fast path (one module-level flag check
  and one profiler check, no lock, no allocation) when tracing is off.
  While the JAX profiler records, every span is also written into the
  profiler's trace, beside the device's operations.
* :mod:`repro.obs.timeline` — per-request lifecycle timelines for the
  serving path (admit -> queue -> pack -> dispatch -> retry -> slice ->
  reply, one event per ``GenRequest`` state edge), joining the serving
  conservation ledger so every terminal state has a timeline.
* :mod:`repro.obs.export` — Chrome-trace/Perfetto JSON export of spans and
  timelines, plus Prometheus-style text exposition of counters, gauges,
  and percentile summaries.
* :mod:`repro.obs.flight_recorder` — bounded ring buffer of recent events
  that dumps a JSON artifact on replica DEAD transitions, NaN-guard trips,
  ``SimulatedCrash``, and SIGTERM.
* :mod:`repro.obs.audit` — the autotune decision audit trail: every
  ``tune_layer`` / ``tune_pair`` race records its candidates, measured
  walls/proxies, and the winner's margin; queryable via
  ``python -m repro.obs``.

Span taxonomy, the request-timeline contract, and the recorder trigger
matrix live in ``docs/OBSERVABILITY.md``.
"""
from repro.obs.audit import AuditTrail, get_trail, set_trail
from repro.obs.export import (
    chrome_trace,
    parse_prometheus_text,
    prometheus_text,
    write_chrome_trace,
)
from repro.obs.flight_recorder import FlightRecorder
from repro.obs.timeline import TERMINAL_EVENTS, RequestTimeline, TimelineStore
from repro.obs.trace import (
    Tracer,
    counter,
    disable,
    enable,
    enabled,
    event,
    gauge,
    get_tracer,
    observe,
    percentiles,
    set_tracer,
    span,
)

__all__ = [
    "AuditTrail", "FlightRecorder", "RequestTimeline", "TERMINAL_EVENTS",
    "TimelineStore", "Tracer", "chrome_trace", "counter", "disable",
    "enable", "enabled", "event", "gauge", "get_tracer", "get_trail",
    "observe", "parse_prometheus_text", "percentiles", "prometheus_text",
    "set_tracer", "set_trail", "span", "write_chrome_trace",
]
