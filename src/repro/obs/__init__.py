"""Unified observability plane: tracing, metrics, streaming, SLOs.

Pillars over the serving fleet:

  * :mod:`repro.obs.trace` — deterministic per-request trace spans over
    the runtime's virtual clocks, exported as Chrome-trace/Perfetto JSON
    (complete spans, instants, and native counter tracks);
  * :mod:`repro.obs.metrics` — a counter/gauge/histogram registry with
    Prometheus-text and canonical-JSON exporters;
  * :mod:`repro.obs.stream` — virtual-clock-driven segment flushes that
    bound recorder memory for long-lived runs, plus segment stitching;
  * :mod:`repro.obs.sampling` — deterministic head+tail per-request trace
    sampling with an always-keep anomaly lane and a hard buffered cap;
  * :mod:`repro.obs.slo` — SLO monitors with multi-window burn-rate
    alerting on the virtual clock;
  * :mod:`repro.obs.scrape` — a localhost HTTP endpoint serving the live
    registry (``/metrics`` Prometheus text, ``/metrics.json``);
  * :mod:`repro.obs.profiling` — the wall-clock layer profiler: spans
    of the scheduler, engine, LM and kernels (each also a
    ``jax.profiler`` annotation) and the XLA compiles charged to them,
    installed through :mod:`repro.common.profile_slot`.

``repro.obs.wiring`` registers the standard serving metric series;
``launch/serve.py`` wires everything into the serving driver
(``--trace-out/--metrics-out/--scrape-every/--trace-sample/--slo-*``),
and ``tools/trace_export.py`` / ``tools/obs_smoke.py`` consume the
artifacts.
"""
from repro.obs.metrics import (
    Counter,
    Gauge,
    HistogramMetric,
    MetricsRegistry,
    MultiGauge,
)
from repro.obs.profiling import LayerProfiler
from repro.obs.sampling import TraceSampler, is_anomaly_event
from repro.obs.scrape import MetricsServer, merge_prom_texts
from repro.obs.slo import (
    BurnRateSLO,
    RollingWindow,
    SLOTracker,
    SpendBurnSLO,
    build_slo_tracker,
)
from repro.obs.stream import ObsFlusher, concat_dir, concat_segments
from repro.obs.trace import (
    WALL_CATS,
    ScopedTrace,
    TraceRecorder,
    build_trace_doc,
    request_trees,
    trace_summary,
    validate_chrome_trace,
    validate_span_tree,
)
from repro.obs.wiring import (
    register_governor_metrics,
    register_plane_metrics,
    register_scheduler_metrics,
    register_slo_metrics,
    register_stream_metrics,
    register_transport_metrics,
)

__all__ = [
    "BurnRateSLO",
    "Counter",
    "Gauge",
    "HistogramMetric",
    "LayerProfiler",
    "MetricsRegistry",
    "MetricsServer",
    "MultiGauge",
    "ObsFlusher",
    "RollingWindow",
    "SLOTracker",
    "ScopedTrace",
    "SpendBurnSLO",
    "TraceRecorder",
    "TraceSampler",
    "WALL_CATS",
    "build_slo_tracker",
    "build_trace_doc",
    "concat_dir",
    "concat_segments",
    "is_anomaly_event",
    "merge_prom_texts",
    "register_governor_metrics",
    "register_plane_metrics",
    "register_scheduler_metrics",
    "register_slo_metrics",
    "register_stream_metrics",
    "register_transport_metrics",
    "request_trees",
    "trace_summary",
    "validate_chrome_trace",
    "validate_span_tree",
]
