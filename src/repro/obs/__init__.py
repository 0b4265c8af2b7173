"""``repro.obs`` - the coherence telemetry plane.

MESI perf counters, per-request span tracing and oracle-verified
metrics for the live coherence service:

  * :mod:`repro.obs.registry` - exact counters / gauges / ring-buffer
    histograms with Prometheus text + JSON snapshot exposition;
  * :mod:`repro.obs.telemetry` - the per-authority ``Telemetry``
    facade: one ``record_batch`` hook per committed micro-batch feeds
    the MESI detectors (invalidation events/storms, ping-pong,
    staleness-at-serve, state occupancy) and the span recorder;
  * :mod:`repro.obs.spans` - phase spans on the profiler's clock, one
    record per committed micro-batch (or sweep call), with Chrome
    trace-event export (``chrome://tracing`` / Perfetto flame graphs);
  * :mod:`repro.obs.runtime` - the process-wide ``jax.monitoring``
    listener that charges trace / lower / compile time to the open
    record, its build-event log, and the sweep-call records;
  * :mod:`repro.obs.stats` - the unified ``stats()`` schema both
    broker flavors serve;
  * :mod:`repro.obs.conformance` - the ``MetricsConformance`` oracle
    leg: every replayable counter recomputed from the captured
    ``ServiceTrace`` and asserted bit-identical to the live registry.

See ``docs/observability.md`` for the metric catalog and the
MESI-analogue rationale behind each counter.
"""

from repro.obs.conformance import (CONFORMANCE_COUNTERS,
                                   CONFORMANCE_HISTOGRAMS,
                                   MetricsConformanceError,
                                   check_metrics_conformance,
                                   replay_telemetry)
from repro.obs.registry import (Counter, Gauge, Histogram,
                                MetricsRegistry)
from repro.obs.runtime import (compile_count, compile_events,
                               reset_compile_log, sweep_records)
from repro.obs.spans import (BatchRecord, Record, Span, SpanRecorder,
                             span)
from repro.obs.stats import unified_stats
from repro.obs.telemetry import BatchObservation, Telemetry

__all__ = [
    "BatchObservation",
    "BatchRecord",
    "CONFORMANCE_COUNTERS",
    "CONFORMANCE_HISTOGRAMS",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsConformanceError",
    "MetricsRegistry",
    "Record",
    "Span",
    "SpanRecorder",
    "Telemetry",
    "check_metrics_conformance",
    "compile_count",
    "compile_events",
    "replay_telemetry",
    "reset_compile_log",
    "span",
    "sweep_records",
    "unified_stats",
]
