"""The contract of ``FedAvgAPI.train()``, the one loop every cell runs: a run
driven as several horizons (the way a resumed run and the benchmark's
``run_rounds`` drive it) is the run driven as one, evaluation rows appear
exactly at the cadence and read the model as of their round, rows kept back
are flushed at the 64th, a resumed run warms up from its own first round,
the flight recorder folds one record a round, and a launch that still asks
for the removed multi-round fusing fails loudly."""

import dataclasses

import jax
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import ModelDef
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.telemetry import TelemetryScope
from fedml_tpu.telemetry.flight import FlightRecorder

NUM_CLIENTS = 10
NUM_CLASSES = 4
FEAT = (6,)


def _data(ragged=False):
    return synthetic_classification(
        num_clients=NUM_CLIENTS, num_classes=NUM_CLASSES, feat_shape=FEAT,
        samples_per_client=24, partition_method="hetero", ragged=ragged, seed=11,
    )


def _model():
    return ModelDef(
        module=LogisticRegression(num_classes=NUM_CLASSES), input_shape=FEAT,
        num_classes=NUM_CLASSES, name="lr",
    )


def _cfg(comm_round, freq, **fed_kw):
    return RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(
            client_num_in_total=NUM_CLIENTS, client_num_per_round=4,
            comm_round=comm_round, epochs=2, frequency_of_the_test=freq,
            **fed_kw,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1, momentum=0.9),
        seed=3,
    )


def _run_horizon(api, first, end):
    """Rounds [first, end) as one ``train()`` call on ``api``: the horizon
    is set from outside, as a resumed run's is."""
    fed = dataclasses.replace(api.config.fed, comm_round=end)
    api.config = dataclasses.replace(api.config, fed=fed)
    api.start_round = first
    return api.train()


def _scoped_api(cfg, tenant):
    """An API on a tracer of its own (the process tracer stays untouched)."""
    scope = TelemetryScope(tenant=tenant)
    with scope.activate():
        api = FedAvgAPI(cfg, _data(), _model())
    assert api._tracer is scope.tracer
    return api, scope.tracer


# ---------------------------------------------------------------------------
# horizons
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ragged", [False, True], ids=["even", "ragged"])
@pytest.mark.parametrize("pipeline", ["auto", "off"])
@pytest.mark.parametrize("client_parallelism", ["vmap", "scan"])
def test_split_horizons_are_the_single_run(client_parallelism, pipeline, ragged):
    R, freq = 6, 4
    kw = dict(client_parallelism=client_parallelism, pipeline=pipeline)
    data, model = _data(ragged), _model()
    single = FedAvgAPI(_cfg(R, freq, **kw), data, model)
    final = single.train()
    split = FedAvgAPI(_cfg(1, freq, **kw), data, model)
    for first, end in ((0, 1), (1, 3), (3, R)):
        last = _run_horizon(split, first, end)
        assert last["round"] == end - 1
    assert last == final

    assert [row["round"] for row in single.history] == list(range(R))
    assert [row["round"] for row in split.history] == list(range(R))
    for a, b in zip(single.history, split.history):
        assert a["Train/Loss"] == b["Train/Loss"], a["round"]
        assert a["Train/Acc"] == b["Train/Acc"], a["round"]
    evals = lambda api: [r["round"] for r in api.history if "Test/Loss" in r]
    # a horizon's last round is an evaluation round: the split run has the
    # single run's evaluations and round 2's besides
    assert evals(single) == [0, 4, 5] and evals(split) == [0, 2, 4, 5]
    for r in evals(single):
        assert single.history[r]["Test/Loss"] == split.history[r]["Test/Loss"]
        assert single.history[r]["Test/Acc"] == split.history[r]["Test/Acc"]
    for a, b in zip(
        jax.tree_util.tree_leaves(single.global_vars),
        jax.tree_util.tree_leaves(split.global_vars),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the pipeline prepares no round past a horizon's end
    assert not split._warm_placed and not split._pipeline_overlap
    if pipeline == "auto":
        assert single.pipeline_rounds == R - 1
        assert split.pipeline_rounds == R - 3
    else:
        assert single.pipeline_rounds == split.pipeline_rounds == 0


# ---------------------------------------------------------------------------
# evaluation cadence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("freq", [1, 3, 100])
def test_evaluation_rows_at_the_cadence_read_that_rounds_model(freq):
    R = 7
    data, model = _data(), _model()
    api = FedAvgAPI(_cfg(R, freq), data, model)
    api.train()
    want = [r for r in range(R) if r % freq == 0 or r == R - 1]
    assert [r["round"] for r in api.history if "Test/Loss" in r] == want
    assert all(("Test/Acc" in r) == ("Test/Loss" in r) for r in api.history)
    # an object stepped round by round and stopped at each evaluation round
    stepped = FedAvgAPI(_cfg(R, freq), data, model)
    for r in range(R):
        stepped.train_round(r)
        if r in want:
            loss, acc = stepped.evaluate_global()
            assert api.history[r]["Test/Loss"] == loss, r
            assert api.history[r]["Test/Acc"] == acc, r


# ---------------------------------------------------------------------------
# periodic flush
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("start", [0, 5], ids=["fresh", "resumed"])
def test_rows_kept_back_are_flushed_at_the_64th(start):
    """With no evaluation round in reach the loop keeps the rounds' metrics
    on the device and fetches them 64 at a time; the last flush brings the
    rest and ``train()`` returns the final row."""
    R = start + 70
    api, tracer = _scoped_api(_cfg(R, 1000), f"flush-{start}")
    arrivals = []  # rounds handed to each flush, and the rows logged by then
    inner = api._flush_pending

    def recording_flush(pending):
        rounds = [r for r, _ in pending]
        out = inner(pending)
        arrivals.append((rounds, len(api.history)))
        return out

    api._flush_pending = recording_flush
    api.start_round = start
    final = api.train()

    rounds = list(range(start, R))
    if start == 0:
        # round 0 is an evaluation round (0 % cadence == 0): a flush of one
        batches = [rounds[:1], rounds[1:65], rounds[65:]]
    else:
        batches = [rounds[:64], rounds[64:]]
    # the loop's closing flush finds nothing pending
    assert [a[0] for a in arrivals] == batches + [[]]
    assert [a[1] for a in arrivals] == list(np.cumsum([len(b) for b in batches])) + [
        len(rounds)
    ]
    assert [row["round"] for row in api.history] == rounds
    assert all(np.isfinite(row["Train/Loss"]) for row in api.history)
    assert final == api.history[-1] and final["round"] == R - 1
    assert "Test/Loss" in final  # the last round evaluates

    flushes = [e for e in tracer.events() if e.name == "flush"]
    assert [
        (e.attrs["first_round"], e.attrs["last_round"], e.attrs["rows"])
        for e in flushes
    ] == [(b[0], b[-1], len(b)) for b in batches]
    waits = [e for e in tracer.events() if e.name == "flush_wait"]
    assert [e.attrs["rows"] for e in waits] == [len(b) for b in batches]


# ---------------------------------------------------------------------------
# warm-up of a resumed run
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("client_parallelism", ["vmap", "scan"])
def test_warmup_of_a_resumed_run_warms_and_stashes_its_first_round(client_parallelism):
    """``warmup()`` on an object whose ``start_round`` is past 0 places that
    round's batch, leaves it for ``train()`` to consume, executes nothing,
    and the warm run ends where the cold one does."""
    R, first = 6, 2
    data, model = _data(ragged=True), _model()
    cfg = _cfg(R, 4, client_parallelism=client_parallelism)
    cold = FedAvgAPI(cfg, data, model)
    cold.start_round = first
    cold.train()
    warm = FedAvgAPI(cfg, data, model)
    warm.start_round = first
    before = jax.tree_util.tree_map(np.asarray, warm.global_vars)
    rows = warm.warmup(log_fn=lambda row: None)
    assert "compile/round_compile_s" in rows and "compile/eval_compile_s" in rows
    assert not [k for k in rows if "fused" in k or "chunk" in k]
    assert list(warm._warm_placed) == [first]
    for a, b in zip(
        jax.tree_util.tree_leaves(before),
        jax.tree_util.tree_leaves(warm.global_vars),
    ):
        np.testing.assert_array_equal(a, np.asarray(b))
    warm.train()
    assert not warm._warm_placed
    assert [row["round"] for row in warm.history] == list(range(first, R))
    assert [r["Train/Loss"] for r in warm.history] == [
        r["Train/Loss"] for r in cold.history
    ]
    for a, b in zip(
        jax.tree_util.tree_leaves(cold.global_vars),
        jax.tree_util.tree_leaves(warm.global_vars),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# one record a round
# ---------------------------------------------------------------------------


def test_flight_recorder_folds_one_record_a_round():
    R = 6
    api, tracer = _scoped_api(_cfg(R, 4), "flight")
    recorder = FlightRecorder(max_rounds=32).attach(tracer)
    api.train()
    assert recorder.rounds_folded == R
    tail = recorder.tail()
    assert [rec["round"] for rec in tail] == list(range(R))
    # a record is a round, never a chunk of them
    assert not any("fused" in key for rec in tail for key in rec)
    # every round but the first was prepared while the one before it ran
    assert api.pipeline_rounds == R - 1
    assert ["overlap_s" in rec for rec in tail] == [False] + [True] * (R - 1)
    rounds = [e for e in tracer.events() if e.name == "round"]
    assert [e.attrs["round"] for e in rounds] == list(range(R))
    assert not any("fused" in key for e in rounds for key in e.attrs)
    health = [e for e in tracer.events() if e.name == "health"]
    assert [(e.attrs["first_round"], e.attrs["last_round"]) for e in health] == [
        (r, r) for r in range(R)
    ]


# ---------------------------------------------------------------------------
# a stale launch script fails loudly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stale", [{"fused_rounds": 4}, {"fused_plan": "measured"}],
                         ids=lambda kw: next(iter(kw)))
def test_a_config_that_still_asks_for_fusing_is_refused(stale):
    """A config that still asks for multi-round fusing must not run the one
    loop while believing it fuses."""
    with pytest.raises(TypeError, match="unexpected keyword"):
        FedConfig(**stale)


@pytest.mark.parametrize("flag, value", [("--fused_rounds", "4"), ("--fused_plan", "static")])
def test_a_command_line_that_still_asks_for_fusing_is_refused(flag, value):
    from click.testing import CliRunner

    from fedml_tpu.cli import main

    result = CliRunner().invoke(
        main, ["--model", "lr", "--dataset", "synthetic", "--comm_round", "1",
               flag, value],
    )
    assert result.exit_code == 2, result.output
    assert "No such option" in result.output and flag in result.output
