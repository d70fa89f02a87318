"""bigdl_tpu.observability — unified runtime telemetry.

The TPU-native observability subsystem (the reference treats metrics as
first-class — optim/Metrics.scala over Spark accumulators; this is the
equivalent for one-process-per-host JAX):

- **Metrics registry** (``metrics``): thread-safe ``Counter`` /
  ``Gauge`` / ``Histogram`` instruments with labels, near-zero cost when
  disabled. The process default is ``REGISTRY``.
- **Span tracer** (``tracing``): ``with trace.span("train/step"):``
  wall-time trees, nested per thread, forwarded to
  ``jax.profiler.TraceAnnotation`` when available.
- **Flight recorder** (``events``): a bounded ring of per-request
  structured events (submitted → admitted → prefill → first token →
  per-token decode → finished), near-zero cost when disabled — the
  "what happened to request X, in what order" black box.
- **Chrome trace export** (``chrometrace``): span trees + recorder
  events as one Perfetto/``chrome://tracing`` JSON timeline.
- **Postmortems** (``postmortem``): on an engine crash, one JSON
  artifact with the last-N events, open span trees, metrics snapshot,
  and in-flight request states.
- **Device memory** (``memory``): a ``DeviceMemoryMonitor`` sampling
  HBM bytes in use / peak / limit per device with per-pool byte
  attribution (``register_pool`` hooks fed by the serving engine's KV
  pools, the prefix cache, and the optimizers) — the "who owns the
  HBM" layer behind ``GET /debug/memory``.
- **Profiler** (``profiler``): bounded on-demand ``jax.profiler``
  capture — ``capture(seconds)`` programmatically, or
  ``GET/POST /debug/profile?seconds=N`` with zero redeploys.
- **Usage accounting** (``accounting``): a per-request
  ``UsageLedger`` metering queue wait, prefill/decode tokens,
  prefix-reuse savings, KV byte-seconds held, and device-seconds
  attributed pro-rata per dispatch — aggregated per ``tenant=`` under
  a cardinality cap, with engine goodput (padding waste, utilization,
  tokens per device-second) behind ``GET /debug/usage``.
- **Watchdogs** (``watchdog``): ``RecompileWatchdog`` (post-warmup
  compile growth → recompile-storm alert) and ``SloWatchdog``
  (burn-rate evaluation of latency objectives over the TTFT /
  inter-token / queue-wait histograms) — alert gauges, flight-recorder
  events, and the engine's degraded-``/healthz`` state.
- **Anomaly detection** (``anomaly``): online detectors (EWMA
  z-score, sustained threshold, rate-of-change, iteration-fed stall)
  over the timeseries rings, with warmup, hysteresis, and cooldown —
  plus a ``DetectorBank`` converging watchdog alerts onto the same
  trigger stream.
- **Incidents** (``incidents``): an ``IncidentManager`` that turns a
  trigger into a self-contained evidence bundle (windowed event
  slice, phase-attributed slow-request exemplars, memory/stats
  blocks, config digest), deduped under cooldown, ring-bounded in
  memory and on disk, behind ``GET /debug/incidents``.
- **Cost model** (``costmodel``): per-dispatch FLOPs/bytes extracted
  once from XLA's ``cost_analysis`` on the lowered (never compiled)
  programs, with analytic transformer fallbacks and a per-device-kind
  peak table (env-overridable) — achieved FLOP/s, arithmetic
  intensity, compute-vs-memory-bound roofline class, and the
  ``bigdl_serving_mfu`` / ``bigdl_serving_membw_util`` gauges, plus
  the ``LoopPhaseAccumulator`` attributing device-idle time to named
  engine-loop bubbles.
- **Time series** (``timeseries``): a background ``TimeSeriesSampler``
  snapshotting gauges/derived rates into bounded rings behind
  ``GET /debug/timeseries``, rendered as a self-contained SVG-sparkline
  dashboard at ``GET /debug/dashboard`` — plus the fleet merge
  (``merge_fleet_timeseries``) folding every replica's rings onto one
  clock-aligned timeline, rendered with per-replica overlays at the
  front door's ``GET /debug/fleet/dashboard``.
- **SLO error budgets** (``slo_budget``): ``SloBudgetTracker`` turning
  the watchdog's objective snapshots into multi-window (fast/slow
  burn) error-budget accounting — budget-remaining fraction,
  exhaustion ETA at the current burn, per objective and per priority
  class, with a chaos-drillable synthetic-spend path — behind
  ``stats()["slo_budget"]`` and budget bars on both dashboards.
- **Capacity model** (``capacity``): ``estimate_capacity`` combining
  loop-phase fractions, roofline classes, and the usage ledger's
  device-seconds-per-request into per-replica sustainable request
  rate / tokens/s, headroom, replicas-needed what-ifs, and the
  prefill-vs-decode disaggregation projection — behind
  ``stats()["capacity"]`` and ``GET /debug/fleet/capacity``.
- **Exporters** (``exporters``): Prometheus text rendering, a
  stdlib-only ``/metrics`` + ``/healthz`` HTTP endpoint with
  ``/debug/events`` + ``/debug/requests`` + ``/debug/trace`` +
  ``/debug/memory`` + ``/debug/profile`` + ``/debug/timeseries`` +
  ``/debug/dashboard`` routes, and a bridge mirroring the registry
  into ``visualization`` TensorBoard writers.

Wired through the stack: ``Optimizer``/``DistriOptimizer`` (step time,
throughput, loss, lr, grad norm, JIT compiles, checkpoint latency),
``GenerationService``/``PredictionService`` (queue wait, batch
occupancy, dispatch latency, tokens/sec), ``parallel.Engine`` (topology)
and ``bench.py`` (Prometheus snapshots alongside BENCH json).

Quick start::

    from bigdl_tpu import observability as obs

    server = obs.start_http_server(port=9090)   # scrape /metrics
    ...
    print(obs.render_prometheus())              # or render in-process
    obs.trace.render()                          # last span trees

``disable()`` turns every built-in instrument mutation into a no-op
(one boolean check — the hot loops stay unmeasurable).
"""

from bigdl_tpu.observability.metrics import (
    DEFAULT_BUCKETS, Metric, MetricRegistry, REGISTRY,
    default_registry, set_default_registry,
)
from bigdl_tpu.observability.tracing import (
    DEVICE_SCOPES, Span, Tracer, self_ns, trace,
)
from bigdl_tpu.observability.events import (
    Event, FlightRecorder, RECORDER, default_recorder, next_request_id,
    percentile_summary, record, set_default_recorder,
)
from bigdl_tpu.observability.chrometrace import (
    chrome_trace_events, render_chrome_trace, write_chrome_trace,
)
from bigdl_tpu.observability.fleettrace import (
    FLEET_HOPS, estimate_clock_offset, hop_breakdown,
    merge_fleet_trace, merge_request_timelines, mint_trace_id,
    parse_traceparent, render_fleet_trace, write_fleet_trace,
)
from bigdl_tpu.observability.postmortem import (
    build_postmortem, registry_snapshot, write_postmortem,
)
from bigdl_tpu.observability.exporters import (
    MetricsHTTPServer, PROMETHEUS_CONTENT_TYPE, TensorBoardBridge,
    render_prometheus, render_snapshot_prometheus, start_http_server,
    write_prometheus,
)
from bigdl_tpu.observability.instruments import (
    FRACTION_BUCKETS, OCCUPANCY_BUCKETS, OccupancyStats, TIME_BUCKETS,
    bench_instruments, engine_instruments, fleet_instruments,
    generation_instruments, memory_instruments, parallel_instruments,
    serving_bench_instruments, serving_engine_instruments,
    serving_instruments, tenant_usage_instruments, train_instruments,
    watchdog_instruments,
)
from bigdl_tpu.observability.accounting import UsageLedger, UsageRecord
from bigdl_tpu.observability.costmodel import (
    DispatchCostModel, LoopPhaseAccumulator, device_peaks, peak_flops,
    program_cost,
)
from bigdl_tpu.observability.timeseries import (
    TimeSeriesSampler, merge_fleet_timeseries, render_dashboard,
    render_fleet_dashboard,
)
from bigdl_tpu.observability.slo_budget import (
    DEFAULT_BURN_WINDOWS, SloBudgetTracker,
)
from bigdl_tpu.observability.capacity import (
    aggregate_fleet_capacity, estimate_capacity, replicas_needed,
)
from bigdl_tpu.observability.memory import (
    DeviceMemoryMonitor, default_monitor, pool_sizes, register_pool,
    register_owned_pools, static_pools, tree_bytes, tree_device_bytes,
    unregister_pool,
)
from bigdl_tpu.observability.profiler import (
    ProfilerBusy, ProfilerUnavailable, capture,
)
from bigdl_tpu.observability.watchdog import (
    RecompileWatchdog, SloObjective, SloWatchdog,
)
from bigdl_tpu.observability.anomaly import (
    AnomalyDetector, DetectorBank, EwmaZScoreDetector,
    RateOfChangeDetector, StallDetector, ThresholdDetector,
    default_detector_bank,
)
from bigdl_tpu.observability.incidents import (
    INCIDENT_SCHEMA, IncidentManager, classify_timeline, load_incident,
)
from bigdl_tpu.observability.instruments import incident_instruments

__all__ = [
    "DEFAULT_BUCKETS", "Metric", "MetricRegistry", "REGISTRY",
    "default_registry", "set_default_registry",
    "DEVICE_SCOPES", "Span", "Tracer", "self_ns", "trace",
    "Event", "FlightRecorder", "RECORDER", "default_recorder",
    "set_default_recorder", "record", "next_request_id",
    "percentile_summary",
    "chrome_trace_events", "render_chrome_trace", "write_chrome_trace",
    "FLEET_HOPS", "estimate_clock_offset", "hop_breakdown",
    "merge_fleet_trace", "merge_request_timelines", "mint_trace_id",
    "parse_traceparent", "render_fleet_trace", "write_fleet_trace",
    "build_postmortem", "registry_snapshot", "write_postmortem",
    "MetricsHTTPServer", "PROMETHEUS_CONTENT_TYPE", "TensorBoardBridge",
    "render_prometheus", "render_snapshot_prometheus",
    "start_http_server", "write_prometheus",
    "FRACTION_BUCKETS", "OCCUPANCY_BUCKETS", "OccupancyStats",
    "TIME_BUCKETS",
    "bench_instruments", "engine_instruments", "fleet_instruments",
    "generation_instruments", "memory_instruments",
    "parallel_instruments",
    "serving_bench_instruments", "serving_engine_instruments",
    "serving_instruments", "tenant_usage_instruments",
    "train_instruments", "watchdog_instruments",
    "UsageLedger", "UsageRecord",
    "DispatchCostModel", "LoopPhaseAccumulator", "device_peaks",
    "peak_flops", "program_cost",
    "TimeSeriesSampler", "merge_fleet_timeseries", "render_dashboard",
    "render_fleet_dashboard",
    "DEFAULT_BURN_WINDOWS", "SloBudgetTracker",
    "aggregate_fleet_capacity", "estimate_capacity", "replicas_needed",
    "DeviceMemoryMonitor", "default_monitor", "pool_sizes",
    "register_pool", "register_owned_pools", "static_pools",
    "tree_bytes", "tree_device_bytes", "unregister_pool",
    "ProfilerBusy", "ProfilerUnavailable", "capture",
    "RecompileWatchdog", "SloObjective", "SloWatchdog",
    "AnomalyDetector", "DetectorBank", "EwmaZScoreDetector",
    "RateOfChangeDetector", "StallDetector", "ThresholdDetector",
    "default_detector_bank",
    "INCIDENT_SCHEMA", "IncidentManager", "classify_timeline",
    "load_incident", "incident_instruments",
    "enable", "disable", "enabled",
]


def enable() -> None:
    """Re-enable metric recording, span tracing, and the flight
    recorder process-wide."""
    default_registry().enable()
    trace.enable()
    default_recorder().enable()


def disable() -> None:
    """Disable metric recording, span tracing, and the flight recorder
    process-wide (every instrument mutation becomes a boolean check
    and an early return)."""
    default_registry().disable()
    trace.disable()
    default_recorder().disable()


def enabled() -> bool:
    return default_registry().enabled
