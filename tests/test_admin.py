"""Admin control plane (serve/admin.py, serve/admission.py, the
method-aware exporter route table): bearer auth, 405 on wrong verbs,
live tenant add/drain/stop/reload over HTTP, measured admission
pricing + refusals with priced reasons, concurrent admin writes racing
a /metrics scrape, and the bounded per-tenant health registry under a
large-population tenant."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from test_introspect import _assert_valid_exposition

from fedml_tpu.config import (
    AdminConfig,
    DataConfig,
    FedConfig,
    PopulationConfig,
    RunConfig,
    TrainConfig,
)
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import create_model
from fedml_tpu.serve import AdmissionController, FederationServer
from fedml_tpu.telemetry import MetricsRegistry

TOKEN = "test-admin-token"


def _data(num_clients=6, feat=10, seed=0):
    return synthetic_classification(
        num_clients=num_clients, num_classes=3, feat_shape=(feat,),
        samples_per_client=24, partition_method="homo", seed=seed,
    )


def _model(feat=10):
    return create_model("lr", "synthetic", (feat,), 3)


def _cfg(comm_round=3, num_clients=6, per_round=3, seed=0, admin=None,
         population=None):
    kw = {}
    if admin is not None:
        kw["admin"] = admin
    if population is not None:
        kw["population"] = population
    return RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(
            client_num_in_total=num_clients, client_num_per_round=per_round,
            comm_round=comm_round, epochs=1, frequency_of_the_test=100,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        seed=seed,
        **kw,
    )


def _spec(name, comm_round=2):
    """A minimal tenant spec for POST /tenants (single-run CLI keys).
    Every spec is the same model family on purpose: added tenants adopt
    the resident's compiled programs (the PR-9 sharing gate)."""
    return {
        "name": name, "comm_round": comm_round, "client_num_in_total": 6,
        "client_num_per_round": 3, "batch_size": 8, "epochs": 1,
    }


def _req(port, path, method="GET", body=None, token=None, timeout=30):
    """(status, parsed-json-or-text) without raising on HTTP errors."""
    data = None
    headers = {}
    if body is not None:
        data = json.dumps(body).encode() if isinstance(body, dict) else body
        headers["Content-Type"] = "application/json"
    if token is not None:
        headers["Authorization"] = f"Bearer {token}"
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, headers=headers,
        method=method,
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            raw, status, hdrs = resp.read(), resp.status, dict(resp.headers)
    except urllib.error.HTTPError as e:
        raw, status, hdrs = e.read(), e.code, dict(e.headers)
    try:
        return status, json.loads(raw.decode()), hdrs
    except (ValueError, UnicodeDecodeError):
        return status, raw.decode(errors="replace"), hdrs


def _spin(pred, what, timeout=60.0):
    t0 = time.monotonic()
    while not pred():
        assert time.monotonic() - t0 < timeout, f"timed out: {what}"
        time.sleep(0.02)


# ---------------------------------------------------------------------------
# admission controller: measured pricing + deterministic refusals
# ---------------------------------------------------------------------------


def test_admission_controller_prices_and_refuses_deterministically():
    reg = MetricsRegistry()
    ctl = AdmissionController(max_tenants=2, registry=reg)
    cfg, model = _cfg(), _model()
    # under the cap: admitted, with the measured price card attached
    d = ctl.decide("a", cfg, model, live_tenants=1)
    assert d.admit and d.tenant == "a"
    assert d.priced["rss_mb"] is None or d.priced["rss_mb"] > 0
    assert "local_train_digest" in d.priced
    assert "warm_in_process" in d.priced
    # at the cap: refused with the cap in the reason
    d = ctl.decide("b", cfg, model, live_tenants=2)
    assert not d.admit and "max_tenants=2" in d.reason
    # process RSS is always over a 1 MB budget: deterministic refusal
    rss = AdmissionController(max_rss_mb=1.0, registry=reg)
    d = rss.decide("c", cfg, model)
    assert not d.admit and "max_rss_mb=1" in d.reason
    # a tenant DECLARING absurd headroom is refused with the priced gap
    need = AdmissionController(registry=reg)
    cfg_hungry = _cfg(admin=AdminConfig(admit_min_headroom_mb=1e12))
    d = need.decide("d", cfg_hungry, model)
    assert not d.admit and "admit_min_headroom_mb" in d.reason
    assert d.priced["headroom_mb"] is not None
    # every decision landed in the bounded log + the counter
    snap = ctl.snapshot()
    assert snap["admitted"] == 1 and snap["refused"] == 1
    assert [x["decision"] for x in snap["decisions"]] == ["admit", "refuse"]
    body = reg.render()
    assert 'fedml_admission_total{decision="admit"} 1.0' in body
    assert 'fedml_admission_total{decision="refuse"} 3.0' in body


def test_admission_probes_warm_program_digest_of_co_tenant_family():
    """The compile-cost signal: once a same-family co-tenant owns the
    shared local-train program, an identical candidate prices as warm
    (cache_hit_p=1.0, compile ~0) through the SAME key fields the
    factory digests — the one-definition contract."""
    from fedml_tpu.algorithms.fedavg_transport import (
        local_train_key_fields,
        shared_local_train,
    )
    from fedml_tpu.compile import program_digest

    cfg, model = _cfg(seed=7), _model(feat=9)
    ctl = AdmissionController(registry=MetricsRegistry())
    before = ctl.price(cfg, model)
    digest = program_digest(local_train_key_fields(model, cfg, "classification"))
    assert before["local_train_digest"] == digest[:16]
    # register the family's program (what a co-tenant's build does)
    shared_local_train(model, cfg, "classification")
    after = ctl.price(cfg, model)
    assert after["warm_in_process"] is True
    assert after["cache_hit_p"] == 1.0
    d = ctl.decide("warm", cfg, model)
    assert d.admit and "warm in process" in d.reason


# ---------------------------------------------------------------------------
# the write surface: auth + verbs
# ---------------------------------------------------------------------------


def test_admin_routes_require_bearer_token_and_reject_get():
    data, model = _data(), _model()
    srv = FederationServer(prom_port=0, admin_token=TOKEN)
    srv.create_session("auth_t", _cfg(comm_round=2), data, model)
    srv.start()
    port = srv.prom_port
    try:
        # a GET scrape of a mutating route is 405 BEFORE any handler
        # (even a valid token cannot make GET mutate)
        status, doc, hdrs = _req(port, "/tenants", token=TOKEN)
        assert status == 405, doc
        assert "POST" in hdrs.get("Allow", "")
        # POST on the read-only surfaces is 405 too
        for path in ("/metrics", "/status", "/compile"):
            status, _, _ = _req(port, path, method="POST", body={})
            assert status == 405, path
        # no token / wrong token -> 401, nothing mutates
        for tok in (None, "wrong"):
            status, doc, _ = _req(
                port, "/tenants", method="POST", body=_spec("sneak"),
                token=tok,
            )
            assert status == 401, doc
            status, _, _ = _req(
                port, "/tenants/auth_t/stop", method="POST", body=b"",
                token=tok,
            )
            assert status == 401
        assert srv.session("auth_t").state != "stopped"
        with pytest.raises(KeyError):
            srv.session("sneak")
        srv.wait()
    finally:
        srv.close()


def test_service_without_token_has_no_write_surface():
    data, model = _data(), _model()
    srv = FederationServer(prom_port=0)  # read-only: no admin_token
    srv.create_session("ro_t", _cfg(comm_round=2), data, model)
    srv.start()
    try:
        status, _, _ = _req(
            srv.prom_port, "/tenants", method="POST", body=_spec("x"),
            token=TOKEN,
        )
        # the route is never installed: 404, not 401/405
        assert status == 404
        srv.wait()
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# live lifecycle over HTTP: add / drain / stop / reload
# ---------------------------------------------------------------------------


def test_admin_add_drain_reload_lifecycle(tmp_path):
    data, model = _data(), _model()
    srv = FederationServer(
        prom_port=0, admin_token=TOKEN, admission=AdmissionController(),
    )
    # a long-lived co-tenant that stays up while we mutate around it
    srv.create_session(
        "resident", _cfg(comm_round=400), data, model,
        restart=2, checkpoint_path=str(tmp_path / "ck"), checkpoint_every=50,
    )
    srv.start()
    port = srv.prom_port
    try:
        # live ADD: the spec body is the serve CLI's tenant-spec keys
        status, doc, _ = _req(
            port, "/tenants", method="POST", body=_spec("added"),
            token=TOKEN,
        )
        assert status == 201, doc
        assert doc["tenant"] == "added"
        assert doc["admission"]["decision"] == "admit"
        added = srv.session("added")
        assert added.state == "running"
        added.wait(120)  # state flips to done at finalize, not mid-run
        assert added.state == "done"
        # duplicate name -> 409
        status, doc, _ = _req(
            port, "/tenants", method="POST", body=_spec("added"), token=TOKEN,
        )
        assert status == 409, doc
        # malformed bodies / specs -> 400, no tenant appears
        for bad in (b"{not json", {"comm_round": 2}, _spec("bad") | {
                "nonsense_key": 1}):
            status, doc, _ = _req(
                port, "/tenants", method="POST", body=bad, token=TOKEN,
            )
            assert status == 400, doc
        # hot-reload SLOs on the resident without touching co-tenants
        status, doc, _ = _req(
            port, "/tenants/resident/reload", method="POST",
            body={"slo_round_s": 45.0, "restart_budget": 5}, token=TOKEN,
        )
        assert status == 200, doc
        assert doc["applied"] == {"slo_round_s": 45.0, "restart_budget": 5}
        resident = srv.session("resident")
        assert resident.restart.budget == 5
        # non-reloadable key -> 400, nothing applied
        status, doc, _ = _req(
            port, "/tenants/resident/reload", method="POST",
            body={"comm_round": 9}, token=TOKEN,
        )
        assert status == 400 and "non-reloadable" in doc["error"]
        # restart_budget on an unsupervised tenant -> 400
        status, doc, _ = _req(
            port, "/tenants/added/reload", method="POST",
            body={"restart_budget": 9}, token=TOKEN,
        )
        assert status == 400 and "not supervised" in doc["error"]
        # reload is all-or-nothing: a malformed budget in a MIXED body
        # must not leave the new SLOs live behind the 400
        status, doc, _ = _req(
            port, "/tenants/resident/reload", method="POST",
            body={"slo_round_s": 0.5, "restart_budget": "five"},
            token=TOKEN,
        )
        assert status == 400 and "restart_budget" in doc["error"]
        wd = resident.scope.slo_watchdog  # the earlier reload created it
        assert wd.policy.round_s == 45.0  # ... and the bad one kept it
        assert resident.restart.budget == 5  # the earlier reload's value
        # unknown tenant / unknown action -> 404
        status, _, _ = _req(
            port, "/tenants/ghost/drain", method="POST", body=b"",
            token=TOKEN,
        )
        assert status == 404
        status, _, _ = _req(
            port, "/tenants/resident/explode", method="POST", body=b"",
            token=TOKEN,
        )
        assert status == 404
        # DRAIN the resident mid-flight: open round completes, state done
        status, doc, _ = _req(
            port, "/tenants/resident/drain", method="POST", body=b"",
            token=TOKEN,
        )
        assert status == 202 and doc["action"] == "drain"
        results = srv.wait(timeout=120)
        assert results["resident"]["ok"], results["resident"]
        assert results["added"]["ok"]
        # the decisions are the /status admission section
        status, st, _ = _req(port, "/status")
        assert status == 200
        assert st["admin_api"] == "enabled"
        assert st["admission"]["admitted"] >= 1
        assert any(
            d["tenant"] == "added" and d["decision"] == "admit"
            for d in st["admission"]["decisions"]
        )
    finally:
        srv.close()


def test_admission_refusal_over_http_carries_priced_reason():
    data, model = _data(), _model()
    srv = FederationServer(
        prom_port=0, admin_token=TOKEN,
        admission=AdmissionController(max_tenants=1),
    )
    srv.create_session("only", _cfg(comm_round=300), data, model)
    srv.start()
    port = srv.prom_port
    try:
        status, doc, _ = _req(
            port, "/tenants", method="POST", body=_spec("excess"),
            token=TOKEN,
        )
        assert status == 409, doc
        assert "max_tenants=1" in doc["error"]
        assert doc["decision"]["decision"] == "refuse"
        assert doc["decision"]["priced"]  # the price card rode along
        with pytest.raises(KeyError):
            srv.session("excess")
        # the refusal is queryable on /status afterwards — the operator's
        # "why was my tenant refused" answer
        _, st, _ = _req(port, "/status")
        refusals = [
            d for d in st["admission"]["decisions"]
            if d["tenant"] == "excess"
        ]
        assert refusals and "max_tenants=1" in refusals[-1]["reason"]
        assert st["admission"]["refused"] == 1
        # ... and on /metrics as the service-level counter
        assert 'fedml_admission_total{decision="refuse"} 1.0' in (
            srv.render_metrics()
        )
        _req(port, "/tenants/only/stop", method="POST", body=b"",
             token=TOKEN)
        srv.wait(timeout=60)
    finally:
        srv.close()


def test_admin_add_whose_build_fails_at_start_is_400_and_name_reusable():
    """A spec that parses and constructs but whose session BUILD rejects
    the config at start (participation faults without deadline_s) must
    answer 400 — not 500 — and unregister the tenant, so the corrected
    spec can immediately reuse the name."""
    data, model = _data(), _model()
    srv = FederationServer(prom_port=0, admin_token=TOKEN)
    srv.create_session("anchor", _cfg(comm_round=2), data, model)
    srv.start()
    port = srv.prom_port
    try:
        bad = _spec("latefail") | {
            "fault_plan": '{"default": {"dropout_p": 0.5}}'
        }
        status, doc, _ = _req(
            port, "/tenants", method="POST", body=bad, token=TOKEN,
        )
        assert status == 400, doc
        assert "deadline" in doc["error"]
        with pytest.raises(KeyError):
            srv.session("latefail")
        # corrected spec, same name: admitted
        status, doc, _ = _req(
            port, "/tenants", method="POST",
            # 8 s: dropout_p=0.5 makes nearly every round wait out its
            # whole deadline, and the subject here is admission, not the
            # deadline (30 s cost the tier-1 clock 22 s it does not have)
            body=bad | {"deadline_s": 8.0}, token=TOKEN,
        )
        assert status == 201, doc
        srv.wait(timeout=120)
    finally:
        srv.close()


def test_negative_content_length_cannot_hang_a_handler_thread():
    """Content-Length: -1 must be clamped, not passed to read(-1) —
    which would block the handler until client EOF, before auth runs."""
    import http.client

    data, model = _data(), _model()
    srv = FederationServer(prom_port=0, admin_token=TOKEN)
    srv.create_session("neg_t", _cfg(comm_round=2), data, model)
    srv.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.prom_port,
                                          timeout=10)
        conn.putrequest("POST", "/tenants")
        conn.putheader("Content-Length", "-1")
        conn.endheaders()  # no body, socket stays open
        resp = conn.getresponse()  # must answer promptly (401: no token)
        assert resp.status == 401
        conn.close()
        srv.wait()
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# satellite: concurrent admin WRITES racing a /metrics scrape
# ---------------------------------------------------------------------------


def test_concurrent_admin_writes_racing_scrape_never_tear_or_500():
    """Extends the PR-12 scrape-under-churn satellite to the WRITE path:
    live HTTP adds/drains and reload writes racing a scrape loop must
    always render a structurally valid exposition and never 500."""
    data, model = _data(), _model()
    srv = FederationServer(
        prom_port=0, admin_token=TOKEN, admission=AdmissionController(),
    )
    srv.create_session("spine", _cfg(comm_round=2000), data, model)
    srv.start()
    port = srv.prom_port
    failures: list = []
    stop = threading.Event()

    def reload_hammer():
        i = 0
        while not stop.is_set():
            status, doc, _ = _req(
                port, "/tenants/spine/reload", method="POST",
                body={"slo_round_s": float(10 + (i % 5))}, token=TOKEN,
            )
            if status != 200:
                failures.append(("reload", status, doc))
            i += 1

    def churn_tenants():
        for i in range(3):
            name = f"churn{i}"
            status, doc, _ = _req(
                port, "/tenants", method="POST",
                body=_spec(name, comm_round=200), token=TOKEN,
            )
            if status != 201:
                failures.append(("add", status, doc))
                continue
            status, doc, _ = _req(
                port, f"/tenants/{name}/drain", method="POST", body=b"",
                token=TOKEN,
            )
            if status != 202:
                failures.append(("drain", status, doc))

    threads = [
        threading.Thread(target=reload_hammer, daemon=True),
        threading.Thread(target=churn_tenants, daemon=True),
    ]
    try:
        for t in threads:
            t.start()
        scrapes = 0
        deadline = time.monotonic() + 6.0
        while time.monotonic() < deadline and threads[1].is_alive():
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30
            ).read().decode()
            _assert_valid_exposition(body)
            status, _, _ = _req(port, "/status")
            assert status == 200
            scrapes += 1
        threads[1].join(timeout=120)
        stop.set()
        threads[0].join(timeout=30)
        assert not failures, failures[:5]
        assert scrapes > 5
        assert not threads[1].is_alive(), "tenant churn never finished"
        _req(port, "/tenants/spine/stop", method="POST", body=b"",
             token=TOKEN)
        results = srv.wait(timeout=120)
        for i in range(3):
            assert results[f"churn{i}"]["ok"], results[f"churn{i}"]
    finally:
        stop.set()
        srv.close()


# ---------------------------------------------------------------------------
# satellite: the status printer reflects placement + admission
# ---------------------------------------------------------------------------


def test_render_status_shows_slice_column_and_admission_sections():
    from fedml_tpu.serve.introspect import render_status

    doc = {
        "uptime_s": 5.0, "tenant_count": 2,
        "tenants": {
            "pinned": {"state": "running", "health": "healthy",
                       "rounds_completed": 3, "rounds_target": 10,
                       "device": "cpu:0-3"},
            "packed": {"state": "running", "health": "healthy",
                       "rounds_completed": 1, "rounds_target": 10,
                       "device": "cpu:4-7"},
        },
        "placement": {
            "cpu:0-3": {"devices": 4, "tenants": ["pinned"], "cost": 1.5},
            "cpu:4-7": {"devices": 4, "tenants": ["packed"], "cost": 0},
        },
        "admission": {
            "admitted": 2, "refused": 1,
            "decisions": [
                {"tenant": "ghost", "decision": "refuse",
                 "reason": "tenant cap: 2 live tenants >= max_tenants=2"},
            ],
        },
    }
    out = render_status(doc)
    # the DEVICE column carries the SLICE label per tenant row
    assert any("pinned" in ln and "cpu:0-3" in ln for ln in out.splitlines())
    assert any("packed" in ln and "cpu:4-7" in ln for ln in out.splitlines())
    assert "placement:" in out
    assert any("cpu:0-3" in ln and "pinned" in ln and "cost 1.5" in ln
               for ln in out.splitlines())
    assert "admission: 2 admitted, 1 refused" in out
    assert any("refuse" in ln and "ghost" in ln and "max_tenants=2" in ln
               for ln in out.splitlines())


# ---------------------------------------------------------------------------
# satellite: large-population tenant with the bounded health registry
# ---------------------------------------------------------------------------


def test_large_population_tenant_health_registry_stays_bounded():
    """Serve x population item-1 remainder: a tenant whose population is
    far larger than its health-registry bound keeps the per-tenant
    ACTIVE record set at the bound (full timing windows only for the
    bounded LRU; evicted clients spill to compact counters), while a
    co-tenant with the default bound is untouched."""
    bound = 8
    big_cfg = _cfg(
        comm_round=6, num_clients=64, per_round=16,
        population=PopulationConfig(health_active_clients=bound),
    )
    srv = FederationServer()
    big = srv.create_session(
        "big_pop", big_cfg, _data(num_clients=64, feat=17),
        _model(feat=17),
    )
    small = srv.create_session(
        "small_pop", _cfg(comm_round=3, seed=3), _data(seed=3), _model(),
    )
    srv.start()
    results = srv.wait(timeout=180)
    assert results["big_pop"]["ok"] and results["small_pop"]["ok"]
    health = big.server.health
    # the bound came from PopulationConfig via from_config — one
    # definition for every runtime
    assert health._clients.capacity == bound
    assert len(health._clients) <= bound
    # the run genuinely exceeded the bound: spilled records exist and
    # total coverage (active + spilled) spans the participants
    assert health.known_client_count() > bound
    assert len(health._clients.spilled) > 0
    # spilled clients still answer with exact counters in the snapshot
    snap = health.snapshot()
    assert len(snap) == health.known_client_count()
    spilled_rows = [
        v for v in snap.values() if v["mean_train_s"] is None
    ]
    assert spilled_rows and all(
        r["rounds_participated"] >= 1 for r in spilled_rows
    )
    # the co-tenant's registry kept ITS default bound (per-tenant
    # isolation of the population knobs)
    assert small.server.health._clients.capacity == 65536
    srv.close()
