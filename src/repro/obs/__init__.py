"""repro.obs — streaming telemetry: spans, counters, model-vs-measured.

Dependency-free layers so anything in the repo can import it:

* :mod:`repro.obs.trace` — recorder primitives.  :class:`NullRecorder`
  (the universal default: every hook is a no-op, zero cost when tracing
  is off), :class:`TraceRecorder` (in-memory spans/counters with a
  Chrome trace-event / Perfetto JSON exporter), ``validate_chrome_trace``
  (schema check for emitted files) and :class:`LatencyHistogram`
  (log-bucketed per-request latencies for serving); ``scope`` and
  ``host_span``, the ``smof.*`` names of device ops and host intervals
  in a ``jax.profiler`` trace.
* :mod:`repro.obs.stream` — :class:`StreamTracer`, the per-tick narrator
  for the pipelined streamer (tick/stage spans by 1F1B phase, queue
  occupancy through the bounded rings, spill byte counters), plus
  ``emit_spill_counters`` for the sequential executor's spill path.
* :mod:`repro.obs.modelcheck` — :class:`ModelCheck` via ``check_stream``:
  measured per-stage latencies, tick counts and queue depths vs the
  Eq. 5/6 predictions and Eq. 1 capacities.
* :mod:`repro.obs.metrics` — :class:`MetricsRegistry`: labeled
  counters/gauges/histograms with ``snapshot()``/``delta_since`` and
  Prometheus text exposition (``metrics_text`` + the strict
  ``parse_metrics_text`` round-trip gate).
* :mod:`repro.obs.slo` — :class:`SloEvaluator`: rolling-window
  pass/warn/breach scoring of fps vs the Eq. 6 roofline, p50/p99 latency
  targets, Eq. 1 stall ratio and spill bandwidth vs the device budget.
* :mod:`repro.obs.flight` — :class:`FlightRecorder`: a bounded ring of
  recent events that dumps a Chrome trace on an SLO breach or ModelCheck
  violation.

Configuration travels as :class:`ObsConfig` on ``CompileSpec`` and
round-trips through ``Compiled.save/load``.
"""
from .flight import FlightRecorder
from .metrics import (REGISTRY, Counter, Gauge, Histogram, MetricsRegistry,
                      escape_label_value, parse_metrics_text)
from .modelcheck import (ContentionCheck, ModelCheck, QueueDepthCheck,
                         StageLatencyCheck, check_contention, check_stream)
from .slo import BREACH, PASS, WARN, SloCheck, SloConfig, SloEvaluator, SloReport
from .stream import StreamTracer, emit_spill_counters
from .trace import (NULL_RECORDER, LatencyHistogram, NullRecorder, ObsConfig,
                    TraceRecorder, host_span, scope, validate_chrome_trace)

__all__ = [
    "ObsConfig",
    "NullRecorder",
    "NULL_RECORDER",
    "TraceRecorder",
    "LatencyHistogram",
    "validate_chrome_trace",
    "scope",
    "host_span",
    "StreamTracer",
    "emit_spill_counters",
    "ModelCheck",
    "StageLatencyCheck",
    "QueueDepthCheck",
    "ContentionCheck",
    "check_stream",
    "check_contention",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "REGISTRY",
    "escape_label_value",
    "parse_metrics_text",
    "SloConfig",
    "SloCheck",
    "SloReport",
    "SloEvaluator",
    "PASS",
    "WARN",
    "BREACH",
    "FlightRecorder",
]
