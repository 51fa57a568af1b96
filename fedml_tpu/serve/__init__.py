"""Continuous federation service — many tenants, one TPU, long-lived.

The single-run CLI runs one federation to completion and exits
(``run_federation``); the north star is a SERVICE holding heavy traffic:
N concurrent federations in one process sharing one device, FedBuff-style
async dispatch as the serving path (3.6-3.8x sync update throughput
when last recorded), elastic client join/leave with backpressure, rolling
checkpoints, and per-tenant observability. This package is that service:

- :mod:`fedml_tpu.serve.session` — :class:`FedSession`: ONE federation's
  entire setup (config, data, model, comm factory, scheduler, fault
  injector, checkpoint state, telemetry) instance-scoped so N sessions
  coexist without process-global state. ``run_federation`` /
  ``run_fedbuff_federation`` are now thin blocking wrappers over it.
- :mod:`fedml_tpu.serve.server` — :class:`FederationServer`: runs N
  sessions concurrently, aggregates their telemetry under ``tenant``
  labels on one Prometheus exporter, writes per-tenant rows into one
  summary.json, drains/stops tenants individually.
- :mod:`fedml_tpu.serve.cli` — ``python -m fedml_tpu serve --spec ...``:
  the multi-tenant entry point (JSON list of run configs).
- :mod:`fedml_tpu.serve.supervisor` — :class:`SupervisedSession`: a
  crashed tenant restarts from its latest rolling checkpoint under
  jittered exponential backoff, bounded by a per-tenant restart budget
  and a crash-loop breaker (self-healing; ``restart=`` on
  ``create_session`` / ``restart_budget`` in a tenant spec).
- :mod:`fedml_tpu.serve.introspect` — :class:`Introspector`: read-only
  JSON endpoints (``/status``, ``/tenants/<name>``, ``/compile``, a
  tenant-aware ``/healthz``) on the Prometheus port, plus the
  ``python -m fedml_tpu status`` pretty-printer.
- :mod:`fedml_tpu.serve.slo` — :class:`SloPolicy` /
  :class:`SloWatchdog`: declarative per-tenant objectives (round time,
  rolling p95, throughput floor, recompile ceiling, straggler fraction)
  evaluated against the flight recorder each round; breaches degrade a
  tenant without consuming restart budget, and ``--slo_strict`` turns
  them into a CI failure.
- :mod:`fedml_tpu.serve.admin` — :class:`AdminApi`: the WRITE path on
  the same port (POST ``/tenants`` to add a tenant live, POST
  ``/tenants/<name>/drain|stop|reload``), bearer-token gated
  (``--admin_token``); GET on a mutating route is 405 by construction.
- :mod:`fedml_tpu.serve.admission` — :class:`AdmissionController`:
  price a candidate tenant from MEASURED signals (warm program digests +
  XLA cost analysis, executable-store hit rate, RSS/headroom) before
  ``create_session`` builds anything; refusals carry their priced
  reason on ``/status`` and in ``fedml_admission_total``.
- :mod:`fedml_tpu.serve.placement` — :class:`DeviceSlice` /
  :class:`Placer`: partition the visible devices into slices and
  bin-pack tenants onto them; a session dispatches on ITS slice via a
  thread-local pin, and the supervisor escalates a crash-looping tenant
  to re-placement on an untried slice.

Co-tenant federations with the same model family share compiled programs
for free: the ProgramCache digest (fedml_tpu/compile/) is process-wide by
design, and the per-scope compile attribution in the recompile sentinel
proves it (``compile/recompiles == 0`` on the second same-family tenant —
the ci.sh soak gate). See docs/SERVING.md."""

from fedml_tpu.serve.admin import AdminApi
from fedml_tpu.serve.admission import (
    AdmissionController,
    AdmissionDecision,
    AdmissionRefused,
)
from fedml_tpu.serve.introspect import Introspector
from fedml_tpu.serve.placement import DeviceSlice, Placer, build_slices
from fedml_tpu.serve.session import FedSession
from fedml_tpu.serve.server import FederationServer
from fedml_tpu.serve.slo import SloPolicy, SloWatchdog
from fedml_tpu.serve.supervisor import (
    RestartBudgetExhausted,
    RestartPolicy,
    SupervisedSession,
)

__all__ = [
    "AdmissionController",
    "AdmissionDecision",
    "AdmissionRefused",
    "AdminApi",
    "DeviceSlice",
    "FedSession",
    "FederationServer",
    "Introspector",
    "Placer",
    "RestartBudgetExhausted",
    "RestartPolicy",
    "SloPolicy",
    "SloWatchdog",
    "SupervisedSession",
    "build_slices",
]
