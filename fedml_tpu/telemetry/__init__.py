"""Unified telemetry subsystem — host-side spans, comm traffic accounting,
client health, and a Prometheus exporter.

The reference FedML has only ad-hoc ``time.perf_counter`` timers and rank-0
wandb logging (SURVEY §5). This package is the framework-level answer to
"where did round N spend its time, which client is the straggler, and how
many bytes crossed each transport":

- :mod:`fedml_tpu.telemetry.spans` — zero-dependency structured tracer.
  ``span("round", round=n)`` context manager, thread-safe, nestable; emits
  Chrome-trace-event JSON loadable in Perfetto. Its clock is its own (epoch
  anchor + monotonic delta), not the ``jax.profiler`` device trace's; with
  ``Tracer.annotate`` set (``utils/profiling.span_annotation``, installed by
  ``FedAvgAPI``) every span is also written into a running profile as a
  ``fedml.<name>`` annotation, on the profiler's clock.
- :mod:`fedml_tpu.telemetry.metrics` — counter/gauge/histogram primitives
  plus a registry that renders Prometheus text exposition format.
- :mod:`fedml_tpu.telemetry.comm` — per-message traffic accounting wired
  once into the ``BaseCommManager`` send/notify path so every transport
  (loopback, shm, gRPC, MQTT) gets byte/message/latency metrics for free.
- :mod:`fedml_tpu.telemetry.health` — server-side per-client health
  registry (last-seen round, participation, train-time percentiles,
  straggler flag) fed from the span stream or explicit observations.
- :mod:`fedml_tpu.telemetry.flight` — round flight recorder: a bounded
  last-K-rounds ring folding the span stream into one record per round
  (phase wall times, comm/compile deltas, straggler spread), with
  rolling p50/p95 gauges and a ``flight/*`` summary block — the live
  substrate behind the serve layer's introspection endpoints and SLO
  watchdogs.
- :mod:`fedml_tpu.telemetry.prometheus` — stdlib-only ``/metrics`` HTTP
  endpoint (off by default; CLI flag ``--prom_port``).
- :mod:`fedml_tpu.telemetry.scope` — thread-scoped
  :class:`TelemetryScope` (per-tenant tracer/registry/comm meter) for the
  multi-tenant federation service (fedml_tpu/serve/); the ``get_*``
  accessors consult the active scope and fall back to the process
  globals, so single-run paths are byte-identical. One exporter serves
  every tenant through :class:`TenantedRegistryView` (``tenant`` label).

Everything here is stdlib-only on purpose: telemetry must be importable
before (and without) jax, and must never add a hot-path dependency."""

from fedml_tpu.telemetry.comm import CommMeter, get_comm_meter
from fedml_tpu.telemetry.flight import FlightRecorder
from fedml_tpu.telemetry.health import ClientHealthRegistry
from fedml_tpu.telemetry.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    TenantedRegistryView,
    get_global_registry,
    get_registry,
)
from fedml_tpu.telemetry.prometheus import PrometheusExporter
from fedml_tpu.telemetry.scope import (
    TelemetryScope,
    activate_scope,
    current_scope,
    wrap_in_current_scope,
)
from fedml_tpu.telemetry.spans import (
    Span,
    SpanEvent,
    Tracer,
    get_global_tracer,
    get_tracer,
    span,
)
from fedml_tpu.telemetry.wire import (
    FleetAggregator,
    TraceContext,
    build_beacon,
    get_fleet,
)

__all__ = [
    "ClientHealthRegistry",
    "CommMeter",
    "Counter",
    "FleetAggregator",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "PrometheusExporter",
    "Span",
    "SpanEvent",
    "TelemetryScope",
    "TenantedRegistryView",
    "TraceContext",
    "Tracer",
    "activate_scope",
    "build_beacon",
    "current_scope",
    "get_comm_meter",
    "get_fleet",
    "get_global_registry",
    "get_global_tracer",
    "get_registry",
    "get_tracer",
    "span",
    "telemetry_summary",
    "wrap_in_current_scope",
]


def telemetry_summary(baseline: dict = None) -> dict:
    """Flat ``{"telemetry/...": value}`` row of the process's comm totals,
    shaped for :class:`fedml_tpu.utils.metrics.MetricsLogger` — forwarding
    this through ``log_fn`` keeps summary.json the single CI oracle.

    ``baseline``: an earlier ``get_comm_meter().snapshot()`` to subtract,
    so a run embedded in a long-lived process (tests, notebook sweeps)
    reports ITS traffic, not the process's lifetime totals."""
    snap = get_comm_meter().snapshot()
    row = {}
    for key in ("messages_sent", "messages_received", "bytes_sent", "bytes_received"):
        total = sum(snap[key].values())
        if baseline:
            total -= sum(baseline.get(key, {}).values())
        row[f"telemetry/comm_{key}"] = total
    # transport retry accounting (core/retry.py) — the CI oracle keys for
    # the flaky-transport chaos gate: a faulted run must show retries > 0
    # with gave_up == 0 and unchanged numerics
    for key, out in (("send_retries", "comm/retries"),
                     ("send_gave_up", "comm/gave_up")):
        total = sum(snap.get(key, {}).values())
        if baseline:
            total -= sum(baseline.get(key, {}).values())
        row[out] = total
    # uplink payload accounting (core/compression.py): as-shipped vs
    # fp32-equivalent bytes of the client model updates — the quantized-
    # uplink byte cut is read off these keys in summary.json (the ci.sh
    # gate divides raw by payload), never asserted from codec math
    for key, out in (
        ("uplink_payload_bytes", "comm/uplink_bytes"),
        ("uplink_raw_bytes", "comm/uplink_raw_bytes"),
        ("uplink_updates", "comm/uplink_updates"),
        # downlink mirror (metered at broadcast encode time) + the
        # telemetry-beacon overhead, kept apart from model bytes so the
        # piggyback cost is read, never asserted (telemetry/wire.py)
        ("downlink_payload_bytes", "comm/downlink_bytes"),
        ("downlink_raw_bytes", "comm/downlink_raw_bytes"),
        ("downlink_updates", "comm/downlink_updates"),
        ("beacons", "comm/beacons"),
        ("beacon_bytes", "comm/beacon_bytes"),
    ):
        total = int(snap.get(key, 0))
        if baseline:
            total -= int(baseline.get(key, 0))
        row[out] = total
    return row
