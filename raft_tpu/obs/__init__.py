"""raft_tpu.obs — unified observability: metrics, spans, XLA events.

The reference RAFT instruments every entry point with NVTX ranges and
spdlog (core/nvtx.hpp, core/logger-inl.hpp) and reads the story back
through Nsight.  A TPU serving deployment needs that story *without a
profiler attached*, so this package turns the existing instrumentation
into queryable state:

- :mod:`~raft_tpu.obs.registry` — process-wide thread-safe metrics
  (counters, gauges, labeled histograms with fixed bucket ladders and a
  label-cardinality cap that raises instead of leaking).
- :mod:`~raft_tpu.obs.spans` — structured spans (id, parent, wall time,
  stage timings) fed automatically by ``core.trace.trace_range`` /
  ``@traced``, i.e. every already-instrumented entry point in
  ``neighbors/``, ``cluster/`` and ``serve/`` reports with zero call-site
  churn.
- :mod:`~raft_tpu.obs.xla_events` — ``jax.monitoring`` listeners for
  compile durations, executable-cache hits and transfer events,
  attributed to the enclosing span.
- :mod:`~raft_tpu.obs.export` — Prometheus text format + JSON snapshot.
- :mod:`~raft_tpu.obs.slowlog` — slow-query log with stage breakdowns.
- :mod:`~raft_tpu.obs.profiler` — ``obs.profile(dir)``: one-line
  Perfetto capture.
- :mod:`~raft_tpu.obs.quality` — online recall auditor: shadow-samples
  served batches against an exact oracle off the hot path, with a
  degradation alarm on the recall EWMA.
- :mod:`~raft_tpu.obs.cost` — XLA capacity accounting: per-executable
  FLOPs / bytes / peak memory from ``cost_analysis()`` plus roofline
  utilization and live-buffer gauges per index version.
- :mod:`~raft_tpu.obs.health` — OK/DEGRADED/UNHEALTHY verdicts behind
  ``SearchService.healthz()`` / ``readyz()``.
- :mod:`~raft_tpu.obs.flight` — always-on flight recorder: a bounded
  ring of recent batches with member request ids and per-request
  timelines, auto-dumped (JSON + Perfetto-loadable Chrome trace) on
  health/quality/recompile/exception incidents.
- :mod:`~raft_tpu.obs.events` — bounded in-process pub/sub bus carrying
  every operationally interesting edge (health transitions, quality
  alarms, hot recompiles, batch errors, compaction lifecycle, registry
  swaps, SLO burns); the flight auto-dump is one subscriber.
- :mod:`~raft_tpu.obs.slo` — declarative SLOs with error budgets and
  Google-SRE multi-window multi-burn-rate alerting over availability,
  p99 latency, audited recall and mutation freshness.
- :mod:`~raft_tpu.obs.incidents` — bus subscriber correlating bursts of
  events into incident timelines with service context at open/close,
  exported as JSON + Chrome trace alongside flight dumps.
- :mod:`~raft_tpu.obs.autotune` — closed-loop SLO autotuner: walks each
  served index's warmed effort ladder (through the serve
  ``EffortArbiter``) toward max QPS subject to recall ≥ floor and a
  healthy p99 error budget, navigating the measured QPS–recall
  :class:`FrontierModel` a ``bench frontier`` sweep emits.
- :mod:`~raft_tpu.obs.explain` — per-query EXPLAIN plans: on-demand
  deep explains (``SearchService.explain``) joined from the existing
  instruments, plus an always-on tail-sampled :class:`QueryArchive`
  that retains full plans for the interesting tail and dumps alongside
  flight records into the correlated incident timeline.
- :mod:`~raft_tpu.obs.gateway` — stdlib-only operational HTTP server
  over this whole pull surface: ``/metrics`` (content-negotiated
  Prometheus/OpenMetrics), ``/healthz``/``/readyz`` load-balancer
  probes, snapshot/incident/flight/explain/autotune debug endpoints and
  a token-guarded admin plane; owned by ``SearchService(gateway=True)``
  or run standalone via ``python -m raft_tpu.obs.gateway``.

Quick start::

    from raft_tpu import obs
    obs.install()                      # XLA listeners + span/slowlog merge
    ... build / search / serve ...
    print(obs.snapshot())              # JSON-safe dict
    print(obs.to_prometheus())         # scrape document
    with obs.profile("/tmp/trace"):    # deep dive
        index = ivf_pq.build(params, dataset)

See ``docs/observability.md`` for the guided tour.
"""

from raft_tpu.obs.cost import (
    CostReport,
    analyze_callable,
    analyze_compiled,
    record_cost,
    refresh_live_buffer_gauges,
)
from raft_tpu.obs.export import (
    OPENMETRICS_CONTENT_TYPE,
    PROMETHEUS_CONTENT_TYPE,
    negotiate_content_type,
    snapshot_json,
    to_openmetrics,
    to_prometheus,
    write_snapshot,
)
from raft_tpu.obs.events import (
    Event,
    EventBus,
    default_bus,
    events_snapshot,
    publish,
    subscribe,
)
from raft_tpu.obs.explain import (
    ExplainPlan,
    QueryArchive,
    TailSampler,
    default_archive,
    explain_snapshot,
)
from raft_tpu.obs.autotune import Autotuner, FrontierModel, FrontierPoint
from raft_tpu.obs.flight import (
    FlightRecorder,
    default_recorder,
    flight_snapshot,
    next_request_id,
)
from raft_tpu.obs.incidents import (
    Incident,
    IncidentManager,
    incidents_snapshot,
)
from raft_tpu.obs.profiler import profile
from raft_tpu.obs.quality import QualityAuditor
from raft_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    LabelCardinalityError,
    MetricsRegistry,
    default_registry,
)
from raft_tpu.obs.slo import AlertPolicy, SloEngine, SloSpec
from raft_tpu.obs.slowlog import slowlog_snapshot
from raft_tpu.obs.spans import (
    Span,
    current_span,
    finish_span,
    open_span,
    recent_spans,
    set_enabled,
    span,
    spans_snapshot,
)
from raft_tpu.obs import (
    autotune,
    cost,
    events,
    explain,
    flight,
    gateway,
    health,
    incidents,
    profiler,
    quality,
    slo,
    slowlog,
    spans,
    xla_events,
)
from raft_tpu.obs.gateway import GatewayConfig, OperationalGateway

registry = default_registry  # `obs.registry()` reads as the obvious accessor


def install() -> None:
    """Activate the full pipeline: XLA monitoring listeners, the span and
    slow-query sections in registry snapshots, and the default event bus
    (whose creation wires the flight auto-dump subscriber and the
    incident manager).  Idempotent."""
    xla_events.install()
    reg = default_registry()
    reg.register_provider("spans", spans_snapshot)
    reg.register_provider("slow_queries", slowlog_snapshot)
    reg.register_provider("flight", flight_snapshot)
    reg.register_provider("explain", explain_snapshot)
    events.default_bus()


def snapshot():
    """JSON-safe snapshot of the process registry (counters, gauges,
    histograms, plus every registered provider section)."""
    return default_registry().snapshot()


__all__ = [
    "AlertPolicy",
    "Autotuner",
    "CostReport",
    "Counter",
    "Event",
    "EventBus",
    "ExplainPlan",
    "FlightRecorder",
    "FrontierModel",
    "FrontierPoint",
    "Gauge",
    "GatewayConfig",
    "Histogram",
    "Incident",
    "IncidentManager",
    "LabelCardinalityError",
    "MetricsRegistry",
    "OPENMETRICS_CONTENT_TYPE",
    "OperationalGateway",
    "PROMETHEUS_CONTENT_TYPE",
    "QualityAuditor",
    "QueryArchive",
    "SloEngine",
    "SloSpec",
    "Span",
    "TailSampler",
    "analyze_callable",
    "analyze_compiled",
    "autotune",
    "cost",
    "current_span",
    "default_archive",
    "default_bus",
    "default_recorder",
    "default_registry",
    "events",
    "events_snapshot",
    "explain",
    "explain_snapshot",
    "finish_span",
    "flight",
    "gateway",
    "health",
    "incidents",
    "incidents_snapshot",
    "install",
    "negotiate_content_type",
    "next_request_id",
    "open_span",
    "profile",
    "profiler",
    "publish",
    "quality",
    "recent_spans",
    "record_cost",
    "refresh_live_buffer_gauges",
    "registry",
    "set_enabled",
    "slo",
    "slowlog",
    "snapshot",
    "snapshot_json",
    "span",
    "spans",
    "subscribe",
    "to_openmetrics",
    "to_prometheus",
    "write_snapshot",
    "xla_events",
]
