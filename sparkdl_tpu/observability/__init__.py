"""Metrics, tracing, profiling and health — the observability the
reference lacks.

The reference's story is "Spark executor logs + whatever TF timeline offers"
(SURVEY.md §5 "Tracing / profiling": absent as a subsystem; "Metrics": thin
stdout piping). The TPU build makes this first-class, around one spine:

* :mod:`sparkdl_tpu.observability.registry` — the process-wide
  :func:`registry` of counters / gauges / bucketed histograms every layer
  (serving, prefetch, batching, training, checkpointing) reports into;
  ``registry().snapshot()`` is the one-call JSON view, and
  :func:`snapshot_across_hosts` rolls it up over a multi-host job;
* :mod:`sparkdl_tpu.observability.exporters` — Prometheus ``/metrics``
  endpoint (opt-in via ``SPARKDL_TPU_METRICS_PORT``);
* :mod:`sparkdl_tpu.observability.tracing` — ``span("decode", ...)``
  request/step tracing with contextvar propagation and Chrome
  ``trace_event`` JSON export (Perfetto-loadable, next to
  ``jax.profiler`` captures); span wall times feed the
  ``sparkdl_stage_seconds`` histogram so per-stage p50/p95/p99 ride the
  same registry;
* :mod:`sparkdl_tpu.observability.metrics` — step-time / examples-per-sec
  per chip / MFU / infeed-starvation meters, with compiled-FLOPs lookup from
  XLA cost analysis;
* :mod:`sparkdl_tpu.observability.profiling` — ``jax.profiler`` trace
  capture (Perfetto/XPlane) as a context manager plus a per-host trace
  server, and :func:`profile_block` host stack sampling (collapsed-stack
  output, ``SPARKDL_TPU_PROFILE=1`` in the benches);
* :mod:`sparkdl_tpu.observability.health` — device/collective health probe
  run before ``jax.distributed`` training starts (SURVEY.md §5 "Failure
  detection": TPU slice health check before initialize);
* :mod:`sparkdl_tpu.observability.flight` — the flight recorder: bounded
  ring of reliability events (faults, retries, quarantines, autotune
  decisions, span completions) with reliability-triggered postmortem
  bundles, plus the ``/healthz`` aggregation;
* :mod:`sparkdl_tpu.observability.slo` — declared latency/availability
  objectives with rolling error-budget burn, surfaced in engine
  snapshots, ``sparkdl_slo_*`` gauges and ``/slo.json``.
"""

from sparkdl_tpu.observability.exporters import (
    MetricsServer,
    maybe_start_metrics_server,
)
from sparkdl_tpu.observability.flight import (
    FlightRecorder,
    flight_recorder,
    healthz_report,
    record_event,
    trigger_dump,
)
from sparkdl_tpu.observability.health import HealthReport, check_health
from sparkdl_tpu.observability.metrics import (
    StepMeter,
    aggregate_across_hosts,
    compiled_flops,
    device_peak,
    device_peak_flops,
    percentile,
)
from sparkdl_tpu.observability.profiling import (
    StackProfile,
    maybe_profile,
    profile_block,
    start_trace_server,
    trace,
)
from sparkdl_tpu.observability.registry import (
    MetricsRegistry,
    registry,
    snapshot_across_hosts,
)
from sparkdl_tpu.observability.slo import SLO, SLOTracker, slo_report
from sparkdl_tpu.observability.tracing import (
    attach,
    current_context,
    disable_tracing,
    enable_tracing,
    export_chrome_trace,
    record_span,
    span,
    spans_for_trace,
    tracing_enabled,
)

__all__ = [
    "FlightRecorder",
    "HealthReport",
    "MetricsRegistry",
    "MetricsServer",
    "SLO",
    "SLOTracker",
    "StackProfile",
    "StepMeter",
    "aggregate_across_hosts",
    "attach",
    "check_health",
    "compiled_flops",
    "current_context",
    "device_peak",
    "device_peak_flops",
    "disable_tracing",
    "enable_tracing",
    "export_chrome_trace",
    "flight_recorder",
    "healthz_report",
    "maybe_profile",
    "maybe_start_metrics_server",
    "percentile",
    "profile_block",
    "record_event",
    "record_span",
    "registry",
    "slo_report",
    "snapshot_across_hosts",
    "span",
    "spans_for_trace",
    "start_trace_server",
    "trace",
    "tracing_enabled",
    "trigger_dump",
]
