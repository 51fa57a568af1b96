"""Round pipeline (FedConfig.pipeline): preparing round r+1's host work
while round r's device dispatch is in flight must be byte-identical to the
serial loop — the stash commit point is the same `_warm_placed` contract
warmup uses — and must degrade to serial automatically whenever next
round's inputs depend on this round's outcome (adaptive selection, active
fault plans). Also covers the
transport half: once-per-round broadcast encoding and the quantized int8
downlink (CommConfig.downlink_compression)."""

import dataclasses

import jax
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.algorithms.scaffold import ScaffoldAPI
from fedml_tpu.config import (
    CommConfig,
    DataConfig,
    FedConfig,
    RunConfig,
    TrainConfig,
)
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import ModelDef
from fedml_tpu.models.linear import LogisticRegression

NUM_CLIENTS = 10
NUM_CLASSES = 4
FEAT = (6,)


def _data(ragged=False, total=NUM_CLIENTS):
    return synthetic_classification(
        num_clients=total,
        num_classes=NUM_CLASSES,
        feat_shape=FEAT,
        samples_per_client=24,
        partition_method="hetero",
        ragged=ragged,
        seed=11,
    )


def _model():
    return ModelDef(
        module=LogisticRegression(num_classes=NUM_CLASSES),
        input_shape=FEAT,
        num_classes=NUM_CLASSES,
        name="lr",
    )


def _cfg(pipeline="auto", comm_round=8, **fed_kw):
    return RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(
            client_num_in_total=NUM_CLIENTS,
            client_num_per_round=4,
            comm_round=comm_round,
            epochs=2,
            frequency_of_the_test=3,
            pipeline=pipeline,
            **fed_kw,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1, momentum=0.9),
        seed=3,
    )


def _tree_equal(a, b):
    for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# byte parity: pipelined == serial
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ragged", [False, True])
def test_pipelined_matches_serial(ragged):
    data, model = _data(ragged), _model()
    serial = FedAvgAPI(_cfg("off"), data, model)
    serial.train()
    piped = FedAvgAPI(_cfg("auto"), data, model)
    piped.train()
    assert serial.pipeline_rounds == 0
    assert piped.pipeline_rounds > 0
    _tree_equal(serial.global_vars, piped.global_vars)
    for rs, rp in zip(serial.history, piped.history):
        assert rs["round"] == rp["round"]
        assert rs["Train/Loss"] == rp["Train/Loss"]
        if "Test/Acc" in rs:
            assert rs["Test/Acc"] == rp["Test/Acc"]
    # every prepared stash was consumed — nothing leaked
    assert not piped._warm_placed
    assert not piped._pipeline_overlap


def test_scaffold_pipelined_sharded_state_parity(tmp_path):
    """SCAFFOLD with the sharded on-disk state tier: the prepared batch
    rides the stash while per-client control rows keep their own
    prefetch choreography — pipelined == serial exactly, state included."""

    def mk(pipeline):
        cfg = _cfg(
            pipeline,
            comm_round=4,
            state_store="sharded",
            state_dir=str(tmp_path / pipeline),
        )
        cfg = dataclasses.replace(
            cfg, train=TrainConfig(client_optimizer="sgd", lr=0.1)
        )
        return ScaffoldAPI(cfg, _data(), _model())

    serial, piped = mk("off"), mk("auto")
    serial.train()
    piped.train()
    assert piped.pipeline_rounds > 0
    _tree_equal(serial.global_vars, piped.global_vars)
    _tree_equal(serial.c_server, piped.c_server)
    sampled = sorted(
        {int(i) for r in range(4) for i in serial._round_plan(r)[0]}
    )
    _tree_equal(
        serial._c_store.gather(sampled), piped._c_store.gather(sampled)
    )


# ---------------------------------------------------------------------------
# automatic serial degradation
# ---------------------------------------------------------------------------


def test_fault_plan_forces_serial():
    """A plan with participation faults can shrink round r+1's cohort
    based on draws the scheduler has not made yet — the pipeline must
    stand down, and numerics must match the explicit serial run."""
    plan = '{"seed": 1, "clients": {"2": {"dropout_p": 1.0}}}'
    data, model = _data(), _model()
    piped = FedAvgAPI(_cfg("auto", fault_plan=plan), data, model)
    piped.train()
    assert piped.pipeline_rounds == 0
    serial = FedAvgAPI(_cfg("off", fault_plan=plan), data, model)
    serial.train()
    _tree_equal(serial.global_vars, piped.global_vars)


def test_adaptive_selection_forces_serial():
    """power_of_choice selects round r+1 from losses reported in round r
    — preparing ahead would sample from stale signals."""
    data, model = _data(), _model()
    api = FedAvgAPI(_cfg("auto", selection="power_of_choice"), data, model)
    api.train()
    assert api.pipeline_rounds == 0


def test_unsupported_subclasses_stay_serial():
    from fedml_tpu.algorithms.hierarchical import HierarchicalFedAvgAPI
    from fedml_tpu.parallel.hierarchical_sharded import HierarchicalShardedAPI
    from fedml_tpu.robustness.backdoor import BackdoorFedAvgAPI

    for cls in (HierarchicalFedAvgAPI, HierarchicalShardedAPI, BackdoorFedAvgAPI):
        assert cls._supports_pipeline is False
    assert FedAvgAPI._supports_pipeline is True


def test_pipeline_knob_validated():
    with pytest.raises(ValueError, match="pipeline"):
        FedAvgAPI(_cfg("sometimes"), _data(), _model())


# ---------------------------------------------------------------------------
# flight-recorder honesty + recompile budget
# ---------------------------------------------------------------------------


def test_flight_folds_overlap_additively():
    """Pipelined rounds fold `overlap_s`/`pipeline_depth` onto their
    records and the summary row reports totals; t_s semantics (the SLO
    watchdog's input) are untouched."""
    from fedml_tpu.telemetry import get_tracer
    from fedml_tpu.telemetry.flight import FlightRecorder

    rec = FlightRecorder(max_rounds=16)
    rec.attach(get_tracer())
    try:
        api = FedAvgAPI(_cfg("auto"), _data(), _model())
        api.train()
    finally:
        rec.detach()
    tail = rec.tail()
    overlapped = [r for r in tail if "overlap_s" in r]
    assert len(overlapped) == api.pipeline_rounds > 0
    for r in overlapped:
        assert r["overlap_s"] >= 0.0
        assert r["pipeline_depth"] == 1
        assert r["t_s"] >= 0.0
    row = rec.summary_row()
    assert row["flight/pipelined_rounds"] == api.pipeline_rounds
    assert row["flight/overlap_s"] >= 0.0
    # round 0 has no previous round to hide behind — never pipelined
    assert "overlap_s" not in tail[0]


@pytest.fixture
def warmed_pipelined_api():
    """Warmup runs BEFORE the sentinel starts, so the budget window is
    exactly the post-warmup pipelined train loop."""
    data, model = _data(), _model()
    cold = FedAvgAPI(_cfg("off"), data, model)
    cold.train()
    warm = FedAvgAPI(_cfg("auto"), data, model)
    warm.warmup(log_fn=lambda r: None)
    return cold, warm


@pytest.mark.recompile_budget(0)
def test_pipelined_run_post_warmup_compiles_nothing(
    warmed_pipelined_api, recompile_sentinel
):
    """Preparing round r+1 ahead reuses the exact placement/gather
    programs warmup enumerated — zero lazy compiles, byte parity."""
    cold, warm = warmed_pipelined_api
    warm.train()
    assert warm.pipeline_rounds > 0
    _tree_equal(cold.global_vars, warm.global_vars)


# ---------------------------------------------------------------------------
# transport: once-per-round broadcast + quantized downlink
# ---------------------------------------------------------------------------


def _transport_cfg(dl="none", uplink="none", workers=6):
    return RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(
            client_num_in_total=workers,
            client_num_per_round=workers,
            comm_round=4,
            epochs=1,
            frequency_of_the_test=1,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        comm=CommConfig(downlink_compression=dl, compression=uplink),
        seed=3,
    )


def test_broadcast_shares_one_encoded_payload():
    """Every worker's sync message must reference the SAME host buffers —
    one model copy per round, not one per worker."""
    from fedml_tpu.algorithms.fedavg_transport import FedAvgServerManager
    from fedml_tpu.core.loopback import LoopbackCommManager, LoopbackHub
    from fedml_tpu.core.message import MessageType as MT

    cfg = _transport_cfg()
    srv = FedAvgServerManager(
        cfg, LoopbackCommManager(LoopbackHub(), 0), _model(),
        data=_data(total=6), worker_num=6,
    )
    sent = []
    srv._broadcast = lambda msg: (sent.append(msg), True)[1]
    srv._broadcast_round(MT.S2C_SYNC_MODEL, 0, list(range(6)))
    assert len(sent) == 6
    ref_leaves = jax.tree_util.tree_leaves(sent[0].get(MT.ARG_MODEL_PARAMS))
    for msg in sent[1:]:
        for a, b in zip(
            ref_leaves, jax.tree_util.tree_leaves(msg.get(MT.ARG_MODEL_PARAMS))
        ):
            assert a is b  # identity: shared buffers, no per-worker copy
    # the round's reference model IS the shipped tree
    for a, b in zip(
        ref_leaves, jax.tree_util.tree_leaves(srv.global_vars)
    ):
        assert a is b


def test_downlink_int8_loopback_cuts_bytes_at_close_loss():
    from fedml_tpu.algorithms.fedavg_transport import run_loopback_federation
    from fedml_tpu.telemetry import get_comm_meter

    data, model = _data(total=6), _model()
    base_snap = get_comm_meter().snapshot()
    srv_fp32 = run_loopback_federation(_transport_cfg("none"), data, model)
    mid_snap = get_comm_meter().snapshot()
    srv_int8 = run_loopback_federation(_transport_cfg("int8"), data, model)
    end_snap = get_comm_meter().snapshot()

    def d(a, b, k):
        return b.get(k, 0) - a.get(k, 0)

    # fp32 arm: payload == raw (exact downlink)
    assert d(base_snap, mid_snap, "downlink_payload_bytes") == d(
        base_snap, mid_snap, "downlink_raw_bytes"
    ) > 0
    # int8 arm: >= 2x cut (4x on the q arrays; scales dilute small models)
    pay = d(mid_snap, end_snap, "downlink_payload_bytes")
    raw = d(mid_snap, end_snap, "downlink_raw_bytes")
    assert raw / pay >= 2.0, (raw, pay)
    assert d(mid_snap, end_snap, "downlink_updates") == 4 * 6
    # matched reach: final eval loss within tolerance of the exact arm
    assert abs(
        srv_fp32.history[-1]["Test/Loss"] - srv_int8.history[-1]["Test/Loss"]
    ) < 0.05


def test_downlink_int8_composes_with_uplink_compression():
    """Uplink deltas encode against the dequantized broadcast tree and the
    server decodes against the SAME tree — the round must close with sane
    numerics, proving the two references never diverged."""
    from fedml_tpu.algorithms.fedavg_transport import run_loopback_federation

    data, model = _data(total=6), _model()
    exact = run_loopback_federation(_transport_cfg(), data, model)
    both = run_loopback_federation(
        _transport_cfg("int8", uplink="int8"), data, model
    )
    assert abs(
        exact.history[-1]["Test/Loss"] - both.history[-1]["Test/Loss"]
    ) < 0.05


def test_secure_agg_rejects_downlink_compression():
    from fedml_tpu.algorithms.fedavg_transport import FedAvgServerManager
    from fedml_tpu.core.loopback import LoopbackCommManager, LoopbackHub

    cfg = _transport_cfg("int8")
    cfg = dataclasses.replace(
        cfg, comm=dataclasses.replace(cfg.comm, secure_agg=True)
    )
    with pytest.raises(ValueError, match="mutually exclusive"):
        FedAvgServerManager(
            cfg, LoopbackCommManager(LoopbackHub(), 0), _model(), worker_num=6
        )
