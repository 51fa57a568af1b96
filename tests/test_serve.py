"""Continuous federation service (fedml_tpu/serve/): session lifecycle,
multi-tenant isolation, elastic fleets, rolling checkpoint resume through
the session object, and the per-tenant ops surface.

The single-run transports are exercised elsewhere (test_transport.py,
test_fedbuff.py — which now run THROUGH FedSession via the wrapper entry
points); this module covers what only the service layer adds."""

import json
import os
import time

import jax
import numpy as np
import pytest

from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import create_model
from fedml_tpu.serve import FedSession, FederationServer
from fedml_tpu.telemetry import (
    TelemetryScope,
    TenantedRegistryView,
    get_comm_meter,
    get_global_tracer,
)


def _data(num_clients=6, seed=0):
    return synthetic_classification(
        num_clients=num_clients, num_classes=3, feat_shape=(10,),
        samples_per_client=24, partition_method="homo", seed=seed,
    )


def _model():
    return create_model("lr", "synthetic", (10,), 3)


def _sync_cfg(comm_round=3, workers=3, total=6, seed=0, **fed_kw):
    return RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(
            client_num_in_total=total, client_num_per_round=workers,
            comm_round=comm_round, epochs=1, frequency_of_the_test=100,
            **fed_kw,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        seed=seed,
    )


def _async_cfg(comm_round=4, workers=2, total=6, k=2, seed=0, **fed_kw):
    return _sync_cfg(
        comm_round=comm_round, workers=workers, total=total, seed=seed,
        async_buffer_k=k, **fed_kw,
    )


def _tree_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _spin(pred, what, timeout=60.0):
    t0 = time.monotonic()
    while not pred():
        assert time.monotonic() - t0 < timeout, f"timed out waiting for {what}"
        time.sleep(0.02)


# ---------------------------------------------------------------------------
# telemetry isolation
# ---------------------------------------------------------------------------


def test_scoped_session_isolates_telemetry_from_globals():
    """A scoped session's spans/comm bytes land in ITS scope; the process
    globals — what every single-run path and test observes — stay
    untouched (the instance-scoping contract of the serve subsystem)."""
    data, model = _data(), _model()
    g_events = len(get_global_tracer().events())
    g_msgs = sum(get_comm_meter().snapshot()["messages_sent"].values())
    scope = TelemetryScope(tenant="iso")
    session = FedSession(
        _sync_cfg(), data, model, name="iso", scope=scope,
    )
    server = session.run()
    assert len(server.history) == 3
    # scope observed the federation...
    names = {e.name for e in scope.tracer.events()}
    assert {"round", "broadcast", "aggregate", "local_train"} <= names
    snap = scope.comm_meter.snapshot()
    assert sum(snap["messages_sent"].values()) > 0
    assert sum(snap["bytes_sent"].values()) > 0
    # ...the globals did not
    assert len(get_global_tracer().events()) == g_events
    assert (
        sum(get_comm_meter().snapshot()["messages_sent"].values()) == g_msgs
    )
    # per-tenant health registry lives in the scope's registry
    assert scope.registry.get("fedml_clients_seen") is not None


def test_unscoped_session_inherits_globals():
    """Without a scope the session records into the process globals —
    run_federation's classic behavior (byte-compat for every single-run
    caller, incl. the CLI's --telemetry_dir trace)."""
    data, model = _data(), _model()
    g_tracer = get_global_tracer()
    before = len(g_tracer.events())
    session = FedSession(_sync_cfg(comm_round=2), data, model)
    session.run()
    new = [e.name for e in g_tracer.events()[before:]]
    assert "round" in new and "aggregate" in new


# ---------------------------------------------------------------------------
# many tenants, one process
# ---------------------------------------------------------------------------


def test_federation_server_runs_concurrent_tenants_with_labeled_metrics():
    data, model = _data(), _model()
    srv = FederationServer()
    a = srv.create_session(
        "alpha", _sync_cfg(comm_round=3), data, model, algorithm="fedavg"
    )
    b = srv.create_session(
        "beta", _async_cfg(comm_round=4), data, model, algorithm="fedbuff"
    )
    srv.start()
    results = srv.wait()
    assert results["alpha"]["ok"] and results["beta"]["ok"], results
    assert len(a.history) == 3
    assert b.server.server_steps == 4
    # both tenants' comm traffic accounted separately
    for s in (a, b):
        assert sum(s.scope.comm_meter.snapshot()["messages_sent"].values()) > 0
    # one exposition, tenant labels, exactly one TYPE block per metric
    out = srv.render_metrics()
    assert 'tenant="alpha"' in out and 'tenant="beta"' in out
    sent = [
        ln for ln in out.splitlines()
        if ln.startswith("fedml_comm_messages_sent_total{")
    ]
    assert any('tenant="alpha"' in ln for ln in sent)
    assert any('tenant="beta"' in ln for ln in sent)
    assert out.count("# TYPE fedml_comm_messages_sent_total counter") == 1
    srv.close()


def test_cross_tenant_program_sharing_zero_recompiles():
    """The substrate the service exploits: co-tenant federations of the
    same model family share ONE ProgramCache — the second tenant builds
    no new programs and (when jax.monitoring is present) triggers zero
    backend compiles attributed to its scope, which is the ci.sh soak
    gate's `compile/recompiles == 0`."""
    from fedml_tpu.analysis.sentinel import ensure_backend_listener
    from fedml_tpu.compile import get_program_cache

    data, model = _data(), _model()
    have_monitoring = ensure_backend_listener()
    srv = FederationServer()
    a = srv.create_session(
        "fam_a", _async_cfg(comm_round=3, seed=0), data, model,
        algorithm="fedbuff",
    )
    srv.start(names=["fam_a"])
    a.wait()
    stats_before = get_program_cache().stats()
    b = srv.create_session(
        "fam_b", _async_cfg(comm_round=3, seed=1), data, model,
        algorithm="fedbuff",
    )
    srv.start(names=["fam_b"])
    b.wait()
    stats_after = get_program_cache().stats()
    # tenant B minted no new program objects — pure dedup hits
    assert stats_after["misses"] == stats_before["misses"]
    assert stats_after["hits"] > stats_before["hits"]
    if have_monitoring:
        assert b.scope.recompiles() == 0, b.scope.recompiles()
    srv.close()


def test_tenanted_registry_view_merges_blocks():
    """Same metric name across tenants renders as ONE HELP/TYPE block
    with per-tenant sample lines (strict exposition-format parsers
    reject duplicate blocks)."""
    from fedml_tpu.telemetry import MetricsRegistry

    ra, rb = MetricsRegistry(), MetricsRegistry()
    ra.counter("svc_total", "h", ("k",)).inc(1, k="x")
    rb.counter("svc_total", "h", ("k",)).inc(2, k="x")
    rb.histogram("svc_seconds", "h", buckets=(1.0,)).observe(0.5)
    view = TenantedRegistryView()
    view.add_tenant("a", ra)
    view.add_tenant("b", rb)
    out = view.render()
    assert out.count("# TYPE svc_total counter") == 1
    assert 'svc_total{k="x",tenant="a"} 1.0' in out
    assert 'svc_total{k="x",tenant="b"} 2.0' in out
    assert 'svc_seconds_bucket{tenant="b",le="1.0"} 1.0' in out
    assert 'svc_seconds_count{tenant="b"} 1.0' in out


# ---------------------------------------------------------------------------
# elastic fleets (FedBuff)
# ---------------------------------------------------------------------------


def test_elastic_join_leave_with_backpressure():
    data, model = _data(num_clients=8), _model()
    session = FedSession(
        _async_cfg(comm_round=40, workers=2, total=8), data, model,
        algorithm="fedbuff", max_workers=3,
    )
    session.start()
    _spin(lambda: session.server.server_steps >= 3, "first steps")
    joined = session.add_worker()  # fleet 2 -> 3: admitted
    _spin(lambda: session.server.joins_accepted >= 1, "join accept")
    refused = session.add_worker()  # fleet at max_workers: refused
    _spin(lambda: session.server.joins_refused >= 1, "join refuse")
    left = session.remove_worker()
    assert left is joined  # highest-rank live worker
    _spin(lambda: session.server.leaves >= 1, "leave")
    server = session.wait()
    assert server.server_steps == 40
    assert server.joins_accepted == 1
    assert server.joins_refused == 1
    assert server.leaves == 1
    # backpressure is graceful: the refused worker got FINISH, it is
    # neither orphaned nor an error
    assert refused._got_finish and not refused.orphaned
    assert left.left
    st = session.status()
    assert st["state"] == "done" and st["joins_refused"] == 1


def test_sync_session_rejects_elastic_ops():
    data, model = _data(), _model()
    session = FedSession(_sync_cfg(comm_round=2), data, model)
    with pytest.raises(RuntimeError, match="FedBuff"):
        session.add_worker()


def test_refused_join_is_not_counted_live_later():
    """A refused joiner must not haunt the live count: once later
    admissions grow worker_num past its rank, an uncounted phantom would
    make the fleet permanently appear fuller than it is and refuse joins
    below max_workers forever."""
    data, model = _data(num_clients=8), _model()
    session = FedSession(
        _async_cfg(comm_round=10_000, workers=2, total=8), data, model,
        algorithm="fedbuff", max_workers=3,
    )
    session.start()
    srv = session.server
    _spin(lambda: srv.server_steps >= 2, "steps")
    session.add_worker()                       # rank 3: live 2 -> 3
    _spin(lambda: srv.joins_accepted >= 1, "admit rank 3")
    session.add_worker()                       # rank 4: at max -> refused
    _spin(lambda: srv.joins_refused >= 1, "refuse rank 4")
    session.remove_worker()                    # rank 3 leaves: live 2
    _spin(lambda: srv.leaves >= 1, "rank 3 leave")
    session.add_worker()                       # rank 5: live 2 -> 3
    _spin(lambda: srv.joins_accepted >= 2, "admit rank 5")
    session.remove_worker()                    # rank 5 leaves: live 2
    _spin(lambda: srv.leaves >= 2, "rank 5 leave")
    # worker_num is now 5 and the refused rank 4 never joined: a correct
    # live count reads 2 (< max_workers), so this join MUST be admitted
    session.add_worker()
    _spin(lambda: srv.joins_accepted >= 3, "admit after phantom")
    assert srv.joins_refused == 1
    session.drain()
    session.wait(timeout=60)


def test_fedbuff_rejects_warmup():
    data, model = _data(), _model()
    with pytest.raises(ValueError, match="warmup"):
        FedSession(
            _async_cfg(), data, model, algorithm="fedbuff", warmup=True
        )


def test_failed_build_cleans_up_and_marks_failed():
    """A misconfigured tenant (participation faults without deadline_s)
    must fail at start() WITHOUT leaking the shm tmpdir its default comm
    factory already created — a long-lived service admits many specs."""
    data, model = _data(), _model()
    session = FedSession(
        _sync_cfg(comm_round=2, fault_plan='{"default": {"dropout_p": 0.5}}'),
        data, model, runtime="shm",
    )
    with pytest.raises(ValueError, match="deadline_s"):
        session.start()
    assert session.state == "failed"
    assert session._tmpdir is None  # removed, not leaked


# ---------------------------------------------------------------------------
# drain / stop
# ---------------------------------------------------------------------------


def test_fedbuff_drain_stops_early_and_cleanly():
    data, model = _data(), _model()
    session = FedSession(
        _async_cfg(comm_round=10_000), data, model, algorithm="fedbuff"
    )
    session.start()
    _spin(lambda: session.server.server_steps >= 2, "steps")
    session.drain()
    server = session.wait(timeout=60)
    assert 2 <= server.server_steps < 10_000
    assert session.state == "done"


def test_sync_drain_finishes_open_round_then_stops():
    data, model = _data(), _model()
    hit = []

    def log_fn(row):
        if row.get("round") == 1 and "t_s" in row:
            hit.append(row)
            session.request_stop(drain=True, defer=True)

    session = FedSession(
        _sync_cfg(comm_round=10_000), data, model, log_fn=log_fn
    )
    session.start()
    server = session.wait(timeout=120)
    assert hit, "round 1 never completed"
    # the round that carried the stop completed; no further round opened
    assert server.round_idx == 2
    assert session.state == "done"
    # a redundant hard stop on the finished server is a no-op: no
    # fabricated zero-upload round, no duplicate FINISH storm
    rounds_before = len(server.history)
    session.stop()
    assert len(server.history) == rounds_before
    assert server.round_idx == 2


# ---------------------------------------------------------------------------
# rolling checkpoints + resume through the session object (satellite)
# ---------------------------------------------------------------------------


def _instrumented_dispatch(monkeypatch, seq):
    """Record every freshly-minted FedBuff assignment as (client, tag)."""
    from fedml_tpu.algorithms.fedbuff import FedBuffServerManager

    orig = FedBuffServerManager._dispatch

    def patched(self, worker, msg_type=None, reuse=False):
        if msg_type is None:
            r = orig(self, worker, reuse=reuse)
        else:
            r = orig(self, worker, msg_type, reuse)
        if not reuse and worker in self._outstanding:
            seq.append(tuple(self._outstanding[worker]))
        return r

    monkeypatch.setattr(FedBuffServerManager, "_dispatch", patched)
    return orig


def test_fedbuff_session_kill_and_resume_matches_uninterrupted(
    tmp_path, monkeypatch
):
    """THE serve resume contract, through the session object: kill a
    FedBuff session mid-run (deferred hard stop at step 3, rolling
    checkpoint every flush), resume it, and the continuation must (a)
    re-mint the in-flight assignment stream byte-identically — the
    ``sched``-slot/dispatch-counter re-selection — and (b) land on
    numerics identical to an uninterrupted run. K=1 worker with
    async_buffer_k=1 makes the async pipeline fully sequential, so the
    equality is exact, not approximate. power_of_choice selection makes
    the scheduler's persisted loss map load-bearing (an empty one would
    re-select differently)."""
    data, model = _data(num_clients=8, seed=0), _model()

    def cfg():
        return _async_cfg(
            comm_round=6, workers=1, total=8, k=1, seed=3,
            selection="power_of_choice",
        )

    # uninterrupted reference run, with the dispatch stream recorded
    seq_ref = []
    _instrumented_dispatch(monkeypatch, seq_ref)
    ref = FedSession(cfg(), data, model, algorithm="fedbuff").run()
    assert ref.server_steps == 6
    assert len(seq_ref) == 6  # K=1, k=1: one fresh assignment per step
    monkeypatch.undo()

    # killed run: rolling checkpoint every flush, deferred stop at step 3
    cp = str(tmp_path / "tenant_ck")

    def kill_at_3(row):
        if row.get("server_step") == 3:
            killed.request_stop(drain=False, defer=True)

    killed = FedSession(
        cfg(), data, model, algorithm="fedbuff",
        checkpoint_path=cp, checkpoint_every=1, log_fn=kill_at_3,
    )
    dead = killed.run()
    assert dead.server_steps == 3
    assert os.path.exists(cp + ".npz")

    # resumed run: re-selects the in-flight assignment, finishes 4..6
    seq_resumed = []
    _instrumented_dispatch(monkeypatch, seq_resumed)
    resumed_session = FedSession(
        cfg(), data, model, algorithm="fedbuff",
        checkpoint_path=cp, checkpoint_every=1, resume=True,
    )
    resumed = resumed_session.run()
    monkeypatch.undo()
    assert resumed.server_steps == 6
    # (a) the in-flight cohort: the resumed stream IS the reference
    # stream's tail — same clients, same dispatch tags
    assert seq_resumed == seq_ref[3:], (seq_resumed, seq_ref)
    # (b) numerics: bit-identical to never having died
    _tree_equal(ref.global_vars, resumed.global_vars)


def test_sync_session_rolling_checkpoint_resume(tmp_path):
    """Sync path of the same contract: rolling checkpoints at round
    boundaries, resume re-selects via the scheduler's sched slot and the
    continuation matches the uninterrupted run bit-for-bit (aggregation
    sorts by worker index, so sync loopback rounds are order-independent
    and exactly reproducible)."""
    data, model = _data(num_clients=6, seed=1), _model()

    def cfg():
        return _sync_cfg(comm_round=6, workers=2, total=6, seed=7)

    ref = FedSession(cfg(), data, model).run()

    cp = str(tmp_path / "sync_ck")

    def kill_after_round_2(row):
        if row.get("round") == 2 and "t_s" in row:
            killed.request_stop(drain=True, defer=True)

    killed = FedSession(
        cfg(), data, model,
        checkpoint_path=cp, checkpoint_every=1, log_fn=kill_after_round_2,
    )
    dead = killed.run()
    assert dead.round_idx == 3  # rounds 0..2 ran

    resumed = FedSession(
        cfg(), data, model,
        checkpoint_path=cp, checkpoint_every=1, resume=True,
    ).run()
    assert resumed.round_idx == 6
    _tree_equal(ref.global_vars, resumed.global_vars)


def test_resume_of_completed_checkpoint_is_noop(tmp_path):
    data, model = _data(), _model()
    cp = str(tmp_path / "done_ck")
    FedSession(
        _sync_cfg(comm_round=2), data, model,
        checkpoint_path=cp, checkpoint_every=1,
    ).run()
    again = FedSession(
        _sync_cfg(comm_round=2), data, model,
        checkpoint_path=cp, checkpoint_every=1, resume=True,
    )
    again.start()
    server = again.wait()
    assert again.state == "done"
    assert server.history == []  # nothing re-ran


# ---------------------------------------------------------------------------
# endpoint namespacing (satellite)
# ---------------------------------------------------------------------------


def test_shm_namespace_isolates_concurrent_federations(tmp_path):
    """Two shm federations sharing ONE sock_dir must not collide: the
    namespace lands in the socket filename, so the second session's
    rank-0 listener no longer unlinks the first's. (Before the fix, the
    second constructor stole the live socket — a race, then cross-
    delivery.)"""
    from fedml_tpu.core.shm_comm import ShmCommManager, _addr
    from fedml_tpu.core.message import Message, MessageType as MT

    d = str(tmp_path)
    a0 = ShmCommManager(0, d, namespace="ses_a")
    b0 = ShmCommManager(0, d, namespace="ses_b")  # same rank, same dir
    assert _addr(d, 0, "ses_a") != _addr(d, 0, "ses_b")
    assert os.path.exists(_addr(d, 0, "ses_a"))  # a's listener survived b
    assert os.path.exists(_addr(d, 0, "ses_b"))
    got = []

    class Obs:
        def receive_message(self, t, m):
            got.append((t, m.get("ns")))

    import threading

    a0.add_observer(Obs())
    ta = threading.Thread(target=a0.handle_receive_message, daemon=True)
    ta.start()
    a1 = ShmCommManager(1, d, namespace="ses_a")
    msg = Message(MT.C2S_SEND_STATS, 1, 0)
    msg.add_params("ns", "a")
    a1.send_message(msg)
    _spin(lambda: len(got) == 1, "namespaced delivery")
    assert got == [(MT.C2S_SEND_STATS, "a")]
    for m in (a1, a0, b0):
        m.stop_receive_message()
    ta.join(timeout=10)


def test_concurrent_shm_sessions_share_one_sock_dir(tmp_path, monkeypatch):
    """End-to-end: two shm sessions running at once, both socket dirs
    forced to the SAME directory — only the per-session namespace keeps
    them apart."""
    import shutil
    import tempfile

    # a short directory of its own: under xdist ``tmp_path`` is long enough
    # to push the socket names past what a UNIX socket address holds
    shared = tempfile.mkdtemp(prefix="fs", dir="/tmp")
    try:
        monkeypatch.setattr(tempfile, "mkdtemp", lambda **kw: shared)
        data, model = _data(), _model()
        srv = FederationServer()
        a = srv.create_session(
            "shm_a", _sync_cfg(comm_round=2), data, model, runtime="shm"
        )
        b = srv.create_session(
            "shm_b", _sync_cfg(comm_round=2, seed=5), data, model, runtime="shm"
        )
        srv.start()
        results = srv.wait()
        assert results["shm_a"]["ok"] and results["shm_b"]["ok"], results
        assert len(a.history) == 2 and len(b.history) == 2
        srv.close()
    finally:
        shutil.rmtree(shared, ignore_errors=True)


# ---------------------------------------------------------------------------
# serve CLI
# ---------------------------------------------------------------------------


def test_serve_cli_multi_tenant_spec(tmp_path):
    from click.testing import CliRunner

    from fedml_tpu.serve.cli import serve_main

    spec = {
        "tenants": [
            {
                "name": "s1", "algorithm": "fedavg", "runtime": "loopback",
                "model": "lr", "dataset": "synthetic",
                "client_num_in_total": 6, "client_num_per_round": 3,
                "comm_round": 2, "batch_size": 8,
                "frequency_of_the_test": 2,
            },
            {
                "name": "s2", "algorithm": "fedbuff", "runtime": "loopback",
                "model": "lr", "dataset": "synthetic",
                "client_num_in_total": 6, "client_num_per_round": 2,
                "comm_round": 3, "batch_size": 8, "async_buffer_k": 2,
                "frequency_of_the_test": 100,
            },
        ]
    }
    spec_path = tmp_path / "spec.json"
    spec_path.write_text(json.dumps(spec))
    log_dir = tmp_path / "logs"
    result = CliRunner().invoke(
        serve_main,
        ["--spec", str(spec_path), "--log_dir", str(log_dir)],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    out = json.loads(result.output.strip().splitlines()[-1])
    assert out["s1"]["ok"] and out["s2"]["ok"], out
    # aggregate summary carries per-tenant rows...
    agg = json.loads((log_dir / "summary.json").read_text())
    assert agg["tenants/s1/state"] == "done"
    assert agg["tenants/s2/server_steps"] == 3
    assert agg["tenants/s1/comm_bytes_sent"] > 0
    # ...and each tenant has its own full single-run-shaped summary
    t1 = json.loads((log_dir / "s1" / "summary.json").read_text())
    assert "Test/Acc" in t1


def test_serve_cli_rejects_bad_spec(tmp_path):
    from click.testing import CliRunner

    from fedml_tpu.serve.cli import serve_main

    bad = [{"name": "x", "algorithm": "fedavg", "no_such_flag": 1}]
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(bad))
    result = CliRunner().invoke(serve_main, ["--spec", str(p)])
    assert result.exit_code != 0
    assert "no_such_flag" in result.output
    dup = [{"name": "x"}, {"name": "x"}]
    p.write_text(json.dumps(dup))
    result = CliRunner().invoke(serve_main, ["--spec", str(p)])
    assert result.exit_code != 0
