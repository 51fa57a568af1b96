"""Client scheduling & fault-injection runtime (fedml_tpu/scheduler/).

Contracts pinned here:

- policy determinism: every policy is a pure function of (seed, round,
  context) — two fresh schedulers (a "restart") select identically.
- uniform parity: the ``uniform`` policy IS the reference draw
  (np.random.seed(round) + choice), and the ``client_sampling`` shim
  still delegates to it.
- power-of-choice bias: high-loss clients are over-selected.
- straggler_aware avoidance: telemetry-flagged stragglers are skipped
  while enough fast clients exist.
- sim/transport parity: the vmap simulator and the loopback federation
  select byte-identical per-round cohorts from one config.
- fault-injected quorum rounds complete with the partial cohort
  aggregated at correct sample weights, and the dropout lands in the
  health registry.
- scheduler state survives the checkpoint round-trip, so a resumed run
  re-selects its in-flight cohort.
"""

import json

import jax
import numpy as np
import pytest

from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import ModelDef
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.scheduler import (
    ClientScheduler,
    FaultInjector,
    FaultPlan,
    SelectionContext,
    get_policy,
    make_policy,
    overprovisioned_k,
    select_clients,
)
from fedml_tpu.telemetry import ClientHealthRegistry


def _data(num_clients=6, samples=12):
    return synthetic_classification(
        num_clients=num_clients, num_classes=3, feat_shape=(5,),
        samples_per_client=samples, partition_method="homo", seed=9,
    )


def _model():
    return ModelDef(
        module=LogisticRegression(num_classes=3), input_shape=(5,),
        num_classes=3, name="lr",
    )


def _cfg(**fed_kw):
    base = dict(
        client_num_in_total=6, client_num_per_round=3, comm_round=3,
        epochs=1, frequency_of_the_test=1,
    )
    base.update(fed_kw)
    return RunConfig(
        data=DataConfig(batch_size=-1),
        fed=FedConfig(**base),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        seed=0,
    )


# ---------------------------------------------------------------------------
# policies
# ---------------------------------------------------------------------------


def test_uniform_policy_reference_parity():
    np.random.seed(7)
    expect = np.random.choice(range(50), 10, replace=False)
    got = select_clients(7, 50, 10, policy="uniform")
    assert np.array_equal(got, expect)
    # the back-compat shim delegates to the same draw
    from fedml_tpu.algorithms.fedavg import client_sampling

    assert np.array_equal(client_sampling(7, 50, 10), expect)
    assert np.array_equal(client_sampling(0, 5, 5), np.arange(5))
    with pytest.raises(ValueError):
        client_sampling(0, 4, 5)


@pytest.mark.parametrize(
    "policy", ["uniform", "weighted", "power_of_choice", "straggler_aware"]
)
def test_policy_determinism_across_restarts(policy):
    """A 'restart' (fresh scheduler, same seed/config/fed state) selects
    the same cohorts for every round."""
    counts = np.arange(1, 13) * 4

    def run():
        s = ClientScheduler(
            num_clients=12, k=4, policy=policy, seed=5, sample_counts=counts
        )
        for r in range(6):
            s.report_loss(r, 1.0 + r)  # same feed on both "runs"
        return [s.select(r).tolist() for r in range(8)]

    assert run() == run()


def test_seed_changes_non_uniform_policies():
    counts = np.arange(1, 13) * 4
    a = ClientScheduler(num_clients=12, k=4, policy="weighted", seed=0,
                        sample_counts=counts)
    b = ClientScheduler(num_clients=12, k=4, policy="weighted", seed=1,
                        sample_counts=counts)
    sels_a = [a.select(r).tolist() for r in range(8)]
    sels_b = [b.select(r).tolist() for r in range(8)]
    assert sels_a != sels_b  # seed participates in the draw


def test_weighted_policy_biases_to_large_shards():
    counts = np.ones(20)
    counts[:4] = 100.0  # clients 0-3 hold almost all the data
    ctx = SelectionContext(seed=0, num_clients=20, sample_counts=counts)
    pol = get_policy("weighted")
    hits = np.zeros(20)
    for r in range(200):
        hits[pol.select(r, 4, ctx)] += 1
    assert hits[:4].mean() > 4 * max(hits[4:].mean(), 1.0)


def test_power_of_choice_overselects_high_loss_clients():
    losses = {i: (10.0 if i < 4 else 0.1) for i in range(20)}
    ctx = SelectionContext(seed=0, num_clients=20, losses=losses)
    pol = get_policy("power_of_choice")
    hits = np.zeros(20)
    rounds = 200
    for r in range(rounds):
        sel = pol.select(r, 4, ctx)
        assert len(set(sel.tolist())) == 4
        hits[sel] += 1
    # whenever a high-loss client lands in the candidate set it wins a
    # slot; low-loss clients only fill leftovers
    assert hits[:4].min() > 2 * hits[4:].mean()


def test_power_of_choice_explores_unknown_clients_first():
    # clients with NO reported loss rank as +inf: both must be selected
    losses = {i: 1.0 for i in range(10) if i not in (3, 7)}
    ctx = SelectionContext(seed=0, num_clients=10, losses=losses)
    pol = get_policy("power_of_choice", candidate_factor=10.0)  # all candidates
    sel = set(pol.select(0, 2, ctx).tolist())
    assert sel == {3, 7}


def test_straggler_aware_avoids_flagged_clients():
    reg = ClientHealthRegistry()
    for r in range(8):
        for cid in range(10):
            reg.observe_train(cid, r, 10.0 if cid == 9 else 0.1)
    assert reg.straggler_ids() == [9]
    ctx = SelectionContext(seed=0, num_clients=10, health=reg)
    pol = get_policy("straggler_aware")
    for r in range(30):
        assert 9 not in pol.select(r, 4, ctx)
    # but participation wins when there are not enough fast clients:
    # k=10 of 10 must still include the straggler
    assert 9 in pol.select(0, 10, ctx)


def test_overprovision_wraps_any_policy():
    assert overprovisioned_k(4, 1.5, 100) == 6
    assert overprovisioned_k(4, 1.5, 5) == 5  # clamped to the population
    pol = make_policy("uniform", overprovision_factor=1.5)
    ctx = SelectionContext(seed=0, num_clients=100)
    sel = pol.select(0, 4, ctx)
    assert len(sel) == 6 and len(set(sel.tolist())) == 6
    # parity: the wrapper is exactly the inner policy at ceil(k*factor)
    np.random.seed(0)
    assert np.array_equal(sel, np.random.choice(range(100), 6, replace=False))


def test_unknown_policy_raises():
    with pytest.raises(ValueError, match="unknown selection policy"):
        get_policy("nope")


# ---------------------------------------------------------------------------
# fault plan
# ---------------------------------------------------------------------------


def test_fault_plan_parse_and_determinism():
    spec = json.dumps(
        {
            "seed": 3,
            "default": {"flaky_upload_p": 0.25},
            "clients": {
                "2": {"dropout_p": 0.5, "slowdown_s": 0.1},
                "4": {"crash_at_round": 2},
            },
        }
    )
    a, b = FaultPlan.from_spec(spec), FaultPlan.from_spec(spec)
    for cid in range(6):
        for r in range(10):
            assert a.decide(cid, r) == b.decide(cid, r)
    assert a.has_participation_faults()
    assert not a.decide(4, 1).crashed and a.decide(4, 2).crashed
    assert a.decide(4, 7).crashed  # permanent from crash_at_round on
    # dropout_p=0.5 actually fires sometimes and not always
    drops = [a.decide(2, r).drop for r in range(50)]
    assert any(drops) and not all(drops)
    assert a.decide(2, 0).slowdown_s == 0.1
    # round-trip through to_json
    c = FaultPlan.from_json(a.to_json())
    assert c.decide(2, 13) == a.decide(2, 13)


def test_fault_plan_rejects_malformed():
    with pytest.raises(ValueError, match="not valid JSON"):
        FaultPlan.from_spec("{bad json")
    with pytest.raises(ValueError, match="unknown fault spec keys"):
        FaultPlan.from_spec('{"clients": {"0": {"dropout": 1}}}')
    with pytest.raises(ValueError, match="dropout_p"):
        FaultPlan.from_spec('{"default": {"dropout_p": 1.5}}')
    assert FaultPlan.from_spec("") is None
    assert FaultPlan.from_spec(None) is None


def test_fault_plan_from_file(tmp_path):
    p = tmp_path / "plan.json"
    p.write_text('{"clients": {"1": {"dropout_p": 1.0}}}')
    plan = FaultPlan.from_spec(str(p))
    assert plan.decide(1, 0).drop and not plan.decide(0, 0).drop


# ---------------------------------------------------------------------------
# simulator wiring
# ---------------------------------------------------------------------------


def test_sim_fault_filtering_and_summary(tmp_path):
    from fedml_tpu.algorithms.fedavg import FedAvgAPI

    data, model = _data(), _model()
    cfg = _cfg(fault_plan='{"seed": 1, "clients": {"1": {"crash_at_round": 0}}}')
    rows = []
    api = FedAvgAPI(cfg, data, model, log_fn=rows.append)
    api.train()
    # client 1 never trains: removed from every cohort it was selected for
    for r in range(cfg.fed.comm_round):
        assert 1 not in api._round_plan(r)[0]
    sel_rows = [r for r in rows if "scheduler/selected" in r]
    assert len(sel_rows) == cfg.fed.comm_round
    assert api.faults.counters["crash"] == 1  # one event, not one per round
    assert api.health.faults(1).get("crash") == 1


def test_sim_round_plan_memoizes_fault_decisions():
    data, model = _data(), _model()
    cfg = _cfg(fault_plan='{"seed": 1, "clients": {"2": {"dropout_p": 1.0}}}')
    from fedml_tpu.algorithms.fedavg import FedAvgAPI

    api = FedAvgAPI(cfg, data, model)
    a = api._sample_clients(0)
    b = api._sample_clients(0)  # hierarchical-style direct re-derivation
    assert np.array_equal(a, b)
    assert 2 in api.scheduler.select(0).tolist()  # selected...
    assert 2 not in a.tolist()  # ...then dropped by the plan
    # the dropped client was counted ONCE despite two derivations
    assert api.faults.counters["dropout"] == 1


def test_fedbuff_fault_starvation_raises_instead_of_hanging():
    """A plan that crashes every client must terminate the async run with
    a loud error (decline/re-dispatch would otherwise spin forever with
    the buffer never reaching async_buffer_k)."""
    from fedml_tpu.algorithms.fedbuff import run_fedbuff_loopback

    data, model = _data(), _model()
    cfg = _cfg(
        comm_round=4, async_buffer_k=2, frequency_of_the_test=10,
        fault_plan='{"default": {"crash_at_round": 0}}',
    )
    with pytest.raises(RuntimeError, match="starved"):
        run_fedbuff_loopback(cfg, data, model)


# ---------------------------------------------------------------------------
# sim/transport parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "policy,factor",
    [("uniform", 1.0), ("weighted", 1.5), ("power_of_choice", 1.0)],
)
@pytest.mark.recompile_budget(60)  # standalone worst case ~50 across all
# three params; a cache-key instability recompiling per round would not fit
def test_selection_parity_simulation_vs_transport(
    policy, factor, recompile_sentinel
):
    """Same seed + config ⇒ byte-identical per-round selected-client sets
    in the vmap simulator and the loopback transport federation.

    power_of_choice parity is the PR 4 scheduler follow-up: the vmap round
    program now returns per-client loss vectors, so the simulator biases
    on TRUE per-client losses (not the cohort mean) — the same signal the
    transport reads off its uploads' ARG_TRAIN_LOSS."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.algorithms.fedavg_transport import run_loopback_federation

    data, model = _data(), _model()
    cfg = _cfg(selection=policy, overprovision_factor=factor)
    api = FedAvgAPI(cfg, data, model)
    api.train()
    sim_sel = api.scheduler.selections()

    server = run_loopback_federation(cfg, data, model)
    tr_sel = server.scheduler.selections()
    assert sim_sel == tr_sel
    # overprovisioning actually grew the cohort (and the worker fleet)
    expect_k = overprovisioned_k(
        cfg.fed.client_num_per_round, factor, cfg.fed.client_num_in_total
    )
    assert all(len(v) == expect_k for v in sim_sel.values())
    assert server.worker_num == expect_k


# ---------------------------------------------------------------------------
# fault-injected quorum round (transport)
# ---------------------------------------------------------------------------


def test_fault_injected_quorum_round_aggregates_partial_set():
    """A dropout-injected deadline round completes via the quorum path
    with NO hang, aggregates exactly the survivors at their sample
    weights, and records the dropout in telemetry health."""
    from fedml_tpu.algorithms.fedavg import weighted_average
    from fedml_tpu.algorithms.fedavg_transport import (
        LocalTrainer,
        run_loopback_federation,
    )

    data, model = _data(num_clients=3), _model()
    # min_clients=2 pins the quorum to BOTH survivors: the round closes
    # deterministically on their two uploads (never on a compile-delayed
    # single upload racing the deadline timer)
    cfg = _cfg(
        client_num_in_total=3, client_num_per_round=3, comm_round=1,
        deadline_s=1.0, min_clients=2,
        fault_plan='{"seed": 1, "clients": {"%d": {"dropout_p": 1.0}}}'
        % 0,
    )
    rows = []
    server = run_loopback_federation(cfg, data, model, log_fn=rows.append)
    # round 0 samples all 3 clients; client 0 drops — expected model is the
    # weighted average of ONLY clients 1 and 2's local results
    import jax.numpy as jnp

    w0 = jax.device_get(
        model.init(jax.random.fold_in(jax.random.PRNGKey(cfg.seed), 0))
    )
    locals_ = []
    ns = []
    for cid in (1, 2):
        t = LocalTrainer(cfg, data, model, "classification")
        t.update_dataset(cid)
        w, n = t._train(0, w0)
        locals_.append(w)
        ns.append(float(n))
    stacked = jax.tree_util.tree_map(
        lambda *ls: jnp.stack([jnp.asarray(l) for l in ls]), *locals_
    )
    expect = jax.device_get(
        weighted_average(stacked, jnp.asarray(ns, jnp.float32))
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(server.global_vars),
        jax.tree_util.tree_leaves(expect),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    assert server.health.faults(0).get("dropout") == 1
    faults_row = [r for r in rows if "faults/dropouts" in r]
    assert faults_row and faults_row[-1]["faults/dropouts"] == 1


def test_all_dropped_sync_round_abandons_instead_of_hanging():
    """When the ENTIRE cohort drops, no upload can ever close the round —
    after three barren deadlines the server abandons it with the model
    unchanged and moves on (a wedged federation is worse than a violated
    quorum floor)."""
    from fedml_tpu.algorithms.fedavg_transport import run_loopback_federation

    data, model = _data(num_clients=3), _model()
    cfg = _cfg(
        client_num_in_total=3, client_num_per_round=3, comm_round=2,
        deadline_s=0.3, min_clients=2,
        fault_plan='{"default": {"dropout_p": 1.0}}',
    )
    server = run_loopback_federation(cfg, data, model)
    assert [r["round"] for r in server.history] == [0, 1]
    assert server.abandoned_rounds == 2


def test_zero_weight_shards_do_not_crash_weighted_policies():
    """A zero-sample client shard (possible under the Dirichlet
    partitioner) must not crash the p-weighted draws when the request
    exceeds the non-zero support."""
    counts = np.array([0, 0, 5, 5, 0, 3])
    ctx = SelectionContext(seed=0, num_clients=6, sample_counts=counts)
    sel = get_policy("weighted").select(0, 5, ctx)
    assert len(set(sel.tolist())) == 5
    sel2 = get_policy("power_of_choice").select(0, 4, ctx)
    assert len(set(sel2.tolist())) == 4
    # the weighted mass is still honored: non-zero shards always included
    assert {2, 3, 5} <= set(sel.tolist())


def test_participation_faults_without_deadline_rejected():
    from fedml_tpu.algorithms.fedavg_transport import run_loopback_federation

    data, model = _data(num_clients=3), _model()
    cfg = _cfg(
        client_num_in_total=3, client_num_per_round=3, comm_round=1,
        fault_plan='{"clients": {"0": {"dropout_p": 1.0}}}',
    )
    with pytest.raises(ValueError, match="deadline_s"):
        run_loopback_federation(cfg, data, model)


# ---------------------------------------------------------------------------
# checkpoint round-trip
# ---------------------------------------------------------------------------


def test_scheduler_state_checkpoint_roundtrip(tmp_path):
    from fedml_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

    s = ClientScheduler(num_clients=20, k=4, policy="power_of_choice", seed=2)
    for cid in range(10):
        s.report_loss(cid, float(cid))
    first = [s.select(r).tolist() for r in range(4)]

    p = str(tmp_path / "ckpt")
    save_checkpoint(
        p, {"params": {"w": np.zeros(3, np.float32)}}, round_idx=4,
        sched_state=s.state_dict(),
    )
    _, round_idx, _, _, _, sched_state = load_checkpoint(p)
    assert round_idx == 4 and sched_state is not None

    resumed = ClientScheduler(
        num_clients=20, k=4, policy="power_of_choice", seed=2
    )
    resumed.load_state_dict(sched_state)
    # in-flight rounds re-select identically (memo) and the restored loss
    # map makes FUTURE rounds identical to the uninterrupted stream too
    assert [resumed.select(r).tolist() for r in range(4)] == first
    s.report_loss(3, 99.0)
    resumed.report_loss(3, 99.0)
    assert resumed.select(4).tolist() == s.select(4).tolist()


def test_checkpoint_without_sched_state_loads_none(tmp_path):
    from fedml_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

    p = str(tmp_path / "ckpt")
    save_checkpoint(p, {"params": {"w": np.zeros(2, np.float32)}}, round_idx=1)
    out = load_checkpoint(p)
    assert len(out) == 6 and out[5] is None


# ---------------------------------------------------------------------------
# fault injector accounting
# ---------------------------------------------------------------------------


def test_fault_injector_summary_row_and_crash_dedupe():
    plan = FaultPlan.from_spec('{"clients": {"0": {"crash_at_round": 0}}}')
    reg = ClientHealthRegistry()
    inj = FaultInjector(plan, health=reg)
    for r in range(5):
        inj.record(0, r, "crash")
    inj.record(1, 0, "dropout")
    row = inj.summary_row()
    assert row["faults/crashes"] == 1  # one crash event per client
    assert row["faults/dropouts"] == 1
    assert row["faults/total"] == 2
    assert reg.faults(0) == {"crash": 1}
    assert reg.snapshot()["1"]["faults"] == {"dropout": 1}
