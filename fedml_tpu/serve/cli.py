"""``python -m fedml_tpu serve`` — the multi-tenant service entry point.

Takes a JSON tenant spec (a list of run configs) and runs every tenant
concurrently in one process through :class:`FederationServer`. Spec keys
reuse the single-run CLI's flag names verbatim (model, dataset,
client_num_in_total, comm_round, selection, fault_plan, ...) so a tenant
spec IS a run config — plus the session-level keys:

``name`` (required, unique), ``algorithm`` (fedavg|fedprox|fedopt|
fedbuff), ``runtime`` (loopback|shm|mqtt), ``checkpoint_path``,
``checkpoint_every``, ``resume``, ``max_workers``, ``warmup`` — plus the
self-healing keys (fedml_tpu/serve/supervisor.py): ``restart_budget``
(int — supervise the tenant: a crash restarts it from its rolling
checkpoint, at most this many times), ``restart_backoff_s``,
``restart_backoff_max_s``, ``breaker_window`` — plus the SLO keys
(fedml_tpu/serve/slo.py): ``slo_round_s``, ``slo_p95_round_s``,
``slo_min_rounds_per_s``, ``slo_max_recompiles``,
``slo_straggler_frac`` (breaches flip the tenant to ``degraded`` and
count in ``fedml_slo_breaches_total`` without consuming restart
budget; ``--slo_strict`` turns any breach into exit 4).

Spec document shape: ``{"tenants": [...]}`` or a bare JSON list.

Per tenant the service writes a full per-tenant log dir
(``<log_dir>/<name>/`` — metrics.jsonl + summary.json, the same files a
single run writes) and, into the aggregate ``<log_dir>/summary.json``,
one ``tenants/<name>/...`` row per tenant. ``--prom_port`` serves every
tenant's metrics under a ``tenant`` label from one exporter. See
docs/SERVING.md.

Exit codes — split so soak automation can tell a flaky tenant from a
misconfigured spec: **0** every tenant finished (including "recovered
after N restarts" — the restart count rides the JSON output), **1**
tenant runtime failures, **2** misconfigured spec (parse-time, or a
session build rejecting its config), **3** every failure is a
supervised tenant whose restart budget / crash-loop breaker gave up,
**4** (only under ``--slo_strict``) every tenant finished but at least
one breached a declared SLO."""

from __future__ import annotations

import json
from pathlib import Path

import click

SERVE_ALGORITHMS = ("fedavg", "fedprox", "fedopt", "fedbuff")
SERVE_RUNTIMES = ("loopback", "shm", "mqtt")
# session-level keys consumed here, not by build_config
_SESSION_KEYS = (
    "name", "checkpoint_path", "checkpoint_every", "resume", "max_workers",
)
# supervision keys -> RestartPolicy (fedml_tpu/serve/supervisor.py)
_RESTART_KEYS = (
    "restart_budget", "restart_backoff_s", "restart_backoff_max_s",
    "breaker_window",
)


class _RestartsExhaustedExit(click.ClickException):
    """Every failed tenant is a supervised one whose restarts ran dry —
    exit 3 (flaky tenant), distinct from exit 2 (misconfigured spec)."""

    exit_code = 3


class _SloBreachExit(click.ClickException):
    """--slo_strict and at least one tenant breached a declared SLO —
    exit 4: the run FINISHED (numerics fine, tenants done) but missed
    its objectives. Distinct from runtime failure (1), misconfigured
    spec (2) and restart exhaustion (3) so CI can treat an SLO miss as
    its own signal."""

    exit_code = 4


def _cli_defaults() -> dict:
    """The single-run CLI's full flag surface with its defaults — the
    base every tenant spec overlays, so serve and single-run configs can
    never drift apart."""
    from fedml_tpu.cli import main as single_run

    return {p.name: p.default for p in single_run.params}


def load_spec(text_or_path: str) -> list:
    """Parse a tenant spec: inline JSON or a path to a JSON file."""
    s = str(text_or_path).strip()
    if not s.startswith("{") and not s.startswith("["):
        with open(s) as f:
            doc = json.load(f)
    else:
        doc = json.loads(s)
    tenants = doc.get("tenants") if isinstance(doc, dict) else doc
    if not isinstance(tenants, list) or not tenants:
        raise ValueError(
            "tenant spec must be a non-empty JSON list (or {'tenants': [...]})"
        )
    names = set()
    for t in tenants:
        if not isinstance(t, dict) or not t.get("name"):
            raise ValueError(f"every tenant needs a unique 'name': {t!r}")
        if t["name"] in names:
            raise ValueError(f"duplicate tenant name {t['name']!r}")
        names.add(t["name"])
    return tenants


def build_tenant(spec: dict):
    """(config, data, model, session_kwargs) for one tenant spec; the
    tenant name stays in the spec dict (create_session takes it
    positionally)."""
    from fedml_tpu.cli import build_config
    from fedml_tpu.data import registry as data_registry
    from fedml_tpu.models import create_model

    spec = dict(spec)
    algorithm = spec.get("algorithm", "fedavg")
    runtime = spec.get("runtime", "loopback")
    if algorithm not in SERVE_ALGORITHMS:
        raise click.UsageError(
            f"tenant {spec['name']!r}: serve supports algorithms "
            f"{SERVE_ALGORITHMS}, got {algorithm!r}"
        )
    if runtime not in SERVE_RUNTIMES:
        raise click.UsageError(
            f"tenant {spec['name']!r}: serve supports runtimes "
            f"{SERVE_RUNTIMES}, got {runtime!r}"
        )
    opt = _cli_defaults()
    session_kw = {}
    for key in _SESSION_KEYS:
        if key in spec:
            session_kw[key] = spec.pop(key)
    # SLO keys (serve/slo.py) — declarative per-tenant objectives the
    # watchdog evaluates against the flight recorder each round. A
    # malformed value is a PARSE-TIME spec error (exit 2), like every
    # other guard here — not a runtime failure
    from fedml_tpu.serve.slo import SloPolicy

    try:
        slo = SloPolicy.from_spec(spec)
    except (TypeError, ValueError) as e:
        raise click.UsageError(
            f"tenant {session_kw.get('name')!r}: invalid SLO value — {e}"
        )
    if slo is not None:
        session_kw["slo"] = slo
    restart_kw = {k: spec.pop(k) for k in _RESTART_KEYS if k in spec}
    if restart_kw:
        from fedml_tpu.serve.supervisor import RestartPolicy

        if "restart_budget" not in restart_kw:
            raise click.UsageError(
                f"tenant {session_kw.get('name')!r}: {sorted(restart_kw)} "
                "configure supervision but restart_budget is missing — "
                "set it to supervise this tenant"
            )
        session_kw["restart"] = RestartPolicy(
            budget=int(restart_kw["restart_budget"]),
            backoff_base_s=float(restart_kw.get("restart_backoff_s", 0.25)),
            backoff_max_s=float(
                restart_kw.get("restart_backoff_max_s", 30.0)
            ),
            breaker_window=int(restart_kw.get("breaker_window", 0)),
            seed=int(spec.get("seed", 0) or 0),
        )
    name = session_kw.pop("name")  # passed positionally to create_session
    if "dataset" in spec:  # the CLI's --dataset flag maps to dataset_name
        spec["dataset_name"] = spec.pop("dataset")
    unknown = set(spec) - set(opt) - {"algorithm", "runtime"}
    if unknown:
        raise click.UsageError(
            f"tenant {name!r}: unknown spec keys {sorted(unknown)} "
            "(spec keys are the single-run CLI flag names)"
        )
    opt.update(spec)
    # serve's defaults, not the single-run CLI's (runtime defaults to
    # loopback here, vmap there) — the shared validators below read these
    opt["runtime"] = runtime
    opt["algorithm"] = algorithm
    if algorithm == "fedbuff" and opt.get("async_buffer_k", 0) in (0, None):
        opt["async_buffer_k"] = 10  # the CLI flag default
    if algorithm == "fedbuff" and opt.get("warmup"):
        # mirror the single-run CLI's guard (FedSession raises too, but
        # a spec error should fail at parse time, before data loads)
        raise click.UsageError(
            f"tenant {name!r}: warmup is not supported for "
            "algorithm=fedbuff — its workers stream continuously and "
            "compile on first dispatch; there is no round-0 barrier"
        )
    config = build_config(opt)
    # the single-run CLI's transport-retry guards (chaos without retries
    # is a guaranteed mid-run crash — it must be a parse-time CONFIG
    # error here too, not a runtime failure that burns a supervised
    # tenant's restart budget and reads as flakiness)
    from fedml_tpu.cli import _validate_comm_retry

    try:
        _validate_comm_retry(config, opt)
    except click.UsageError as e:
        raise click.UsageError(f"tenant {name!r}: {e.format_message()}")
    data = data_registry.load(config)
    task = data_registry.task_for_dataset(config.data.dataset)
    sample_shape = tuple(data.client_x[0].shape[1:])
    model = create_model(
        config.model, config.data.dataset, sample_shape, data.num_classes
    )
    session_kw.update(
        algorithm=algorithm,
        runtime=runtime,
        task=task,
        warmup=bool(opt.get("warmup", False)),
    )
    return config, data, model, session_kw


@click.command(name="serve")
@click.option("--spec", required=True,
              help="Multi-tenant spec: inline JSON or a path to a JSON "
                   "file — {'tenants': [{name, algorithm, runtime, "
                   "<single-run CLI flags>...}, ...]} or a bare list")
@click.option("--log_dir", type=click.Path(path_type=Path), default=None,
              help="Aggregate log dir: per-tenant subdirs (<name>/"
                   "summary.json) + one service summary.json with "
                   "tenants/<name>/* rows")
@click.option("--prom_port", type=int, default=None,
              help="Serve every tenant's metrics (tenant label) from one "
                   "/metrics endpoint; 0 picks an ephemeral port")
@click.option("--duration_s", type=float, default=None,
              help="Drain every tenant after this many seconds instead "
                   "of waiting for their comm_round targets (a soak knob)")
@click.option("--stagger_s", type=float, default=0.0,
              help="Delay between tenant starts (lets the first tenant "
                   "of a model family pay the compiles the rest share)")
@click.option("--slo_strict", is_flag=True, default=False,
              help="Exit 4 when any tenant breached a declared SLO "
                   "(slo_round_s / slo_p95_round_s / slo_min_rounds_per_s"
                   " / slo_max_recompiles / slo_straggler_frac spec keys)"
                   " — the CI hook; without it breaches only degrade the "
                   "tenant and land in slo/* summary keys + "
                   "fedml_slo_breaches_total")
@click.option("--admin_token", default=None,
              help="Enable the HTTP WRITE api (POST /tenants, "
                   "/tenants/<name>/drain|stop|reload on the metrics "
                   "port, serve/admin.py) behind this bearer token. "
                   "Without it the service is read-only — a scrape can "
                   "never mutate state. Requires --prom_port")
@click.option("--device_slices", type=int, default=0,
              help="Partition the visible devices into this many slices "
                   "and bin-pack tenants onto them (serve/placement.py; "
                   "a tenant spec pins one with device_slice). 0 = no "
                   "placement, every tenant shares the default device. "
                   "CPU hosts: XLA_FLAGS=--xla_force_host_platform_"
                   "device_count=N provides the devices")
@click.option("--devices_per_slice", type=int, default=0,
              help="Devices per slice (0 = split evenly)")
@click.option("--admit_max_rss_mb", type=float, default=0.0,
              help="Admission control: refuse new tenants once process "
                   "RSS exceeds this many MB (serve/admission.py). 0 = "
                   "off")
@click.option("--admit_max_tenants", type=int, default=0,
              help="Admission control: refuse new tenants past this many "
                   "live tenants. 0 = uncapped")
def serve_main(spec, log_dir, prom_port, duration_s, stagger_s, slo_strict,
               admin_token, device_slices, devices_per_slice,
               admit_max_rss_mb, admit_max_tenants):
    """Run N federation tenants concurrently in one process."""
    import time

    from fedml_tpu.serve.server import FederationServer

    tenants = load_spec(spec)
    if admin_token and prom_port is None:
        raise click.UsageError(
            "--admin_token needs --prom_port: the admin api rides the "
            "metrics/introspection port"
        )
    placer = None
    if device_slices:
        from fedml_tpu.serve.placement import Placer, build_slices

        try:
            placer = Placer(build_slices(device_slices, devices_per_slice))
        except ValueError as e:
            raise click.UsageError(str(e))
    admission = None
    if admit_max_rss_mb or admit_max_tenants or admin_token:
        # any admission knob — or a live admin surface, whose adds must
        # go through the door — installs the controller (thresholds off
        # by default: it prices and logs every decision either way)
        from fedml_tpu.serve.admission import AdmissionController

        admission = AdmissionController(
            max_rss_mb=admit_max_rss_mb, max_tenants=admit_max_tenants
        )
    server = FederationServer(
        log_dir=str(log_dir) if log_dir else None, prom_port=prom_port,
        placer=placer, admission=admission, admin_token=admin_token,
    )
    # config-rejected tenants (spec passed parsing but the session build
    # refused it — e.g. participation faults without deadline_s): isolated
    # per tenant so one bad spec never takes down its co-tenants, and
    # reported as the misconfigured-spec exit class (2), NOT as a flaky
    # tenant
    config_failed = {}
    for t in tenants:
        name = t["name"]
        config, data, model, session_kw = build_tenant(t)
        if log_dir:
            from fedml_tpu.utils import MetricsLogger

            tenant_logger = MetricsLogger(str(Path(log_dir) / name))
            session_kw["log_fn"] = tenant_logger.log
        try:
            server.create_session(name, config, data, model, **session_kw)
        except ValueError as e:
            config_failed[name] = repr(e)
        except Exception as e:
            from fedml_tpu.serve.admission import AdmissionRefused

            if not isinstance(e, AdmissionRefused):
                raise
            # a spec tenant refused at the door is an operator problem
            # exactly like a bad spec: surface it in the misconfigured
            # exit class with the priced reason
            config_failed[name] = repr(e)
    try:
        for i, t in enumerate(tenants):
            name = t["name"]
            if name in config_failed:
                continue
            if i and stagger_s:
                time.sleep(stagger_s)
            try:
                server.start(names=[name])
            except ValueError as e:  # session build rejected the config
                config_failed[name] = repr(e)
        if server.prom_port is not None:
            click.echo(
                f"serve: prometheus metrics on "
                f"http://127.0.0.1:{server.prom_port}/metrics",
                err=True,
            )
        if duration_s:
            deadline = time.monotonic() + float(duration_s)
            while time.monotonic() < deadline and not all(
                s.done for s in server.sessions()
            ):
                time.sleep(min(0.25, max(0.0, deadline - time.monotonic())))
            server.drain()
        results = server.wait()
    finally:
        server.close()
    from fedml_tpu.serve.server import _jsonable

    out = {
        name: {
            "ok": r["ok"],
            "error": r["error"],
            "error_kind": r.get("error_kind"),
            **{k: _jsonable(v) for k, v in r["summary"].items()},
        }
        for name, r in results.items()
    }
    for name, err in config_failed.items():
        out[name] = {"ok": False, "error": err, "error_kind": "config"}
    click.echo(json.dumps(out))
    failed = {
        name: r.get("error_kind") or "runtime"
        for name, r in out.items() if not r["ok"]
    }
    breached = sorted(
        name for name, r in out.items() if r.get("slo/breached")
    )
    if not failed:
        if slo_strict and breached:
            raise _SloBreachExit(
                f"tenants breached their declared SLOs: {breached} "
                "(see slo/* summary keys and fedml_slo_breaches_total)"
            )
        return
    if any(kind == "config" for kind in failed.values()):
        # misconfigured specs take precedence: the operator must fix the
        # spec before the flakiness signal means anything
        raise click.UsageError(
            f"misconfigured tenants: "
            f"{sorted(n for n, k in failed.items() if k == 'config')} "
            f"(all failures: {failed})"
        )
    if all(kind == "restart_exhausted" for kind in failed.values()):
        raise _RestartsExhaustedExit(
            f"flaky tenants exhausted their restart budgets: "
            f"{sorted(failed)}"
        )
    raise click.ClickException(f"tenants failed: {failed}")


if __name__ == "__main__":
    serve_main()
