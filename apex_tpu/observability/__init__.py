"""apex_tpu.observability — unified telemetry for serving + training.

Two pieces, both process-wide and dependency-free:

- :mod:`observability.registry` — :class:`MetricsRegistry` of named,
  optionally-labeled :class:`Counter` / :class:`Gauge` /
  :class:`HistogramMeter` (log-bucketed, p50/p90/p99) metrics with
  snapshot/diff semantics, JSON-lines emission, and Prometheus
  text-format exposition.  The ``apex_tpu.utils`` meters become views
  onto a registry when constructed with ``registry=``.
- :mod:`observability.tracing` — :class:`SpanTracer`, a bounded
  ring-buffer span tracer exporting Chrome trace-event JSON
  (Perfetto-loadable).  The process default records only while a
  ``jax.profiler`` session is active, when every span is also an
  ``apex:<span>`` annotation on the profiler's clock, and costs
  nothing otherwise; ``APEX_TPU_TRACE=/path.json`` or
  :func:`enable_tracing` turns it on for good.
- :mod:`observability.flightrecorder` — :class:`FlightRecorder`, a
  bounded ring of structured per-engine-step records (batch
  composition, admit/shed/preempt/evict decisions, memory occupancy,
  speculation outcomes, pressure, breaker state), disabled by default
  (:data:`NULL_FLIGHT_RECORDER`, zero allocations per step), plus
  :func:`write_postmortem` — the bundle (flight JSONL + metrics
  snapshot + Chrome trace + manifest) auto-dumped on chaos invariant
  violations, audit failures, and breaker-open transitions, rendered
  by ``tools/postmortem.py``.
- :mod:`observability.slo` — :class:`SLOTracker` over per-priority
  :class:`SLOTargets`: TTFT / per-token-decode / deadline attainment
  per class, goodput-vs-throughput token counters, and SLO-debt
  accounting for overload shed/displace decisions
  (``stats()["slo"]``).
- :mod:`observability.opsplane` — :class:`OpsServer`, the embedded
  loopback HTTP ops endpoint (``/healthz``, ``/metrics``,
  ``/statusz``, ``/debug/flight``, ``/debug/requests/<uid>``,
  ``POST /drain`` / ``/postmortem``); off by default
  (``ops_port=`` / ``APEX_TPU_OPS_PORT``), probed by
  ``tools/ops_probe.py``.
- :mod:`observability.watchdog` — :class:`HangWatchdog`, the serve
  loop's dead-man's switch: step-loop heartbeats, a no-progress
  deadline, thread-stack + postmortem capture on stall, and a 503
  ``/healthz`` flip; disabled by default at zero cost
  (:data:`NULL_WATCHDOG`).
- :mod:`observability.programs` — :class:`ProgramAccounting`,
  per-compiled-program call/wall/compile tallies behind the pinned
  ``stats()["programs"]`` table and the
  ``serving_program_*`` registry counters.
- :mod:`observability.scopes` — :data:`DEVICE_SCOPES`, the names of
  the blocks of the device programs, and :func:`device_scope`, the
  ``jax.named_scope`` that admits only them: each XLA operation's
  ``tf_op`` in a profile names its block.

What is instrumented out of the box: the serving step loop (``step``
over retire / apply / plan / chunk-prefill / draft / inputs / launch /
account spans,
per-request enqueue→admit→first-token→finish timelines feeding TTFT /
queue-wait / decode-latency histograms in
``InferenceServer.stats()``), engine compile events, checkpoint
save/restore/publish, and the amp train step (step time, loss-scale
trajectory, overflow skips).  See ``docs/observability.md``.
"""

from apex_tpu.observability.flightrecorder import (
    NULL_FLIGHT_RECORDER,
    POSTMORTEM_ENV,
    FlightRecorder,
    NullFlightRecorder,
    write_postmortem,
)
from apex_tpu.observability.journey import (
    JOURNEYS_ENV,
    NULL_JOURNEY_LOG,
    Journey,
    JourneyContext,
    JourneyLog,
    NullJourneyLog,
    dump_journeys,
    journeys_census,
    merge_exemplars,
    merge_journeys,
    resolve_journeys,
)
from apex_tpu.observability.opsplane import OPS_PORT_ENV, OpsServer
from apex_tpu.observability.programs import (
    NULL_PROGRAM_ACCOUNTING,
    NullProgramAccounting,
    ProgramAccounting,
)
from apex_tpu.observability.registry import (
    Counter,
    Gauge,
    HistogramMeter,
    MetricsRegistry,
    PROMETHEUS_CONTENT_TYPE,
    escape_label_value,
    fleet_prometheus_text,
    series_key,
    snapshot_diff,
)
from apex_tpu.observability.watchdog import (
    NULL_WATCHDOG,
    HangWatchdog,
    NullWatchdog,
)
from apex_tpu.observability.scopes import DEVICE_SCOPES, device_scope
from apex_tpu.observability.slo import SLOPolicy, SLOTargets, SLOTracker
from apex_tpu.observability.tracing import (
    NULL_TRACER,
    NullTracer,
    SpanTracer,
    TRACE_ENV,
    enable_tracing,
    get_tracer,
    set_tracer,
)

__all__ = [
    "Counter",
    "DEVICE_SCOPES",
    "FlightRecorder",
    "Gauge",
    "HangWatchdog",
    "HistogramMeter",
    "JOURNEYS_ENV",
    "Journey",
    "JourneyContext",
    "JourneyLog",
    "MetricsRegistry",
    "NULL_FLIGHT_RECORDER",
    "NULL_JOURNEY_LOG",
    "NULL_PROGRAM_ACCOUNTING",
    "NULL_TRACER",
    "NULL_WATCHDOG",
    "NullFlightRecorder",
    "NullJourneyLog",
    "NullProgramAccounting",
    "NullTracer",
    "NullWatchdog",
    "OPS_PORT_ENV",
    "OpsServer",
    "POSTMORTEM_ENV",
    "PROMETHEUS_CONTENT_TYPE",
    "ProgramAccounting",
    "SLOPolicy",
    "SLOTargets",
    "SLOTracker",
    "SpanTracer",
    "TRACE_ENV",
    "device_scope",
    "dump_journeys",
    "enable_tracing",
    "escape_label_value",
    "fleet_prometheus_text",
    "get_tracer",
    "journeys_census",
    "merge_exemplars",
    "merge_journeys",
    "resolve_journeys",
    "series_key",
    "set_tracer",
    "snapshot_diff",
    "write_postmortem",
]
