"""SCAFFOLD: numpy oracle exactness + drift-regime behavior + state store.

The oracle re-implements Option II of the paper in plain numpy on a tiny
logistic-regression problem (full-batch, 1 epoch, no shuffle effects:
every client's data is one exact batch) and must match the jitted round
bit-for-bit-close over multiple rounds, including the control-variate
stack. The drift test reproduces the paper's claim on a heterogeneous
regime: with many local steps, SCAFFOLD's final training accuracy is at
least FedAvg's.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgAPI, client_sampling
from fedml_tpu.algorithms.scaffold import ScaffoldAPI
from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import create_model

N_CLIENTS, N_CLASSES, FEAT = 4, 3, 6


def _cfg(batch_size=8, epochs=1, rounds=2, per_round=N_CLIENTS, lr=0.1):
    return RunConfig(
        data=DataConfig(batch_size=batch_size, pad_bucket=1),
        fed=FedConfig(
            client_num_in_total=N_CLIENTS,
            client_num_per_round=per_round,
            comm_round=rounds,
            epochs=epochs,
            frequency_of_the_test=10_000,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=lr),
        model="lr",
    )


def _data(samples=8):
    return synthetic_classification(
        num_clients=N_CLIENTS,
        num_classes=N_CLASSES,
        feat_shape=(FEAT,),
        samples_per_client=samples,
        partition_method="hetero",
        ragged=False,
        seed=0,
    )


def _softmax_grads(W, b, x, y):
    """Mean CE grads for logits = xW + b (numpy, fp64)."""
    logits = x @ W + b
    logits -= logits.max(axis=1, keepdims=True)
    p = np.exp(logits)
    p /= p.sum(axis=1, keepdims=True)
    onehot = np.eye(N_CLASSES)[y]
    d = (p - onehot) / x.shape[0]
    return x.T @ d, d.sum(axis=0)


def test_matches_numpy_oracle():
    """batch_size=-1 (full batch) + 1 epoch: one SGD step per client per
    round, no shuffle randomness — the round math is exactly checkable."""
    data = _data(samples=8)
    cfg = _cfg(batch_size=-1, epochs=1, rounds=3, lr=0.2)
    model = create_model("lr", "synthetic", (FEAT,), N_CLASSES)
    api = ScaffoldAPI(cfg, data, model)

    # numpy state
    W = np.asarray(api.global_vars["params"]["linear"]["kernel"], np.float64)
    b = np.asarray(api.global_vars["params"]["linear"]["bias"], np.float64)
    cW = np.zeros_like(W)
    cb = np.zeros_like(b)
    ciW = np.zeros((N_CLIENTS,) + W.shape)
    cib = np.zeros((N_CLIENTS,) + b.shape)
    lr = cfg.train.lr

    for r in range(3):
        api.train_round(r)
        sampled = client_sampling(r, N_CLIENTS, N_CLIENTS)
        dWs, dbs, dcW, dcb, ns = [], [], [], [], []
        for i in sampled:
            x = np.asarray(data.client_x[i], np.float64)
            y = np.asarray(data.client_y[i])
            gW, gb = _softmax_grads(W, b, x, y)
            yW = W - lr * (gW + cW - ciW[i])
            yb = b - lr * (gb + cb - cib[i])
            K = 1.0
            ciW_new = ciW[i] - cW + (W - yW) / (K * lr)
            cib_new = cib[i] - cb + (b - yb) / (K * lr)
            dWs.append(yW - W)
            dbs.append(yb - b)
            dcW.append(ciW_new - ciW[i])
            dcb.append(cib_new - cib[i])
            ciW[i], cib[i] = ciW_new, cib_new
            ns.append(len(y))
        w = np.asarray(ns, np.float64)
        w /= w.sum()
        W = W + np.tensordot(w, np.stack(dWs), axes=1)
        b = b + np.tensordot(w, np.stack(dbs), axes=1)
        frac = len(sampled) / N_CLIENTS
        cW = cW + frac * np.mean(np.stack(dcW), axis=0)
        cb = cb + frac * np.mean(np.stack(dcb), axis=0)

    np.testing.assert_allclose(
        np.asarray(api.global_vars["params"]["linear"]["kernel"]), W,
        rtol=1e-5, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(api.global_vars["params"]["linear"]["bias"]), b,
        rtol=1e-5, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(api.c_server["linear"]["kernel"]), cW, rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(api.c_stack["linear"]["kernel"]), ciW, rtol=1e-5, atol=1e-5
    )


def test_partial_participation_updates_only_sampled_rows():
    data = _data(samples=8)
    cfg = _cfg(batch_size=4, epochs=1, rounds=1, per_round=2)
    model = create_model("lr", "synthetic", (FEAT,), N_CLASSES)
    api = ScaffoldAPI(cfg, data, model)
    api.train_round(0)
    sampled = set(client_sampling(0, N_CLIENTS, 2).tolist())
    ci = np.asarray(api.c_stack["linear"]["kernel"])
    for i in range(N_CLIENTS):
        moved = float(np.abs(ci[i]).sum()) > 0
        assert moved == (i in sampled), (i, sampled, moved)


def test_scaffold_at_least_matches_fedavg_under_drift():
    """Heterogeneous shards + many local steps = client drift; the
    control variates must not do WORSE than FedAvg (paper's headline)."""
    data = _data(samples=24)
    cfg = _cfg(batch_size=8, epochs=8, rounds=30, lr=0.05)
    model = create_model("lr", "synthetic", (FEAT,), N_CLASSES)

    def final_acc(api):
        api.train()
        row = api.local_test_on_all_clients(0)
        return row["Train/Acc"]

    acc_scaffold = final_acc(ScaffoldAPI(cfg, data, model))
    acc_fedavg = final_acc(FedAvgAPI(cfg, data, model))
    assert acc_scaffold >= acc_fedavg - 0.02, (acc_scaffold, acc_fedavg)


def test_checkpoint_resume_preserves_control_variates(tmp_path):
    """Kill-and-resume == uninterrupted, INCLUDING c/c_i: without the
    algo-state checkpoint hooks a resumed SCAFFOLD silently restarts the
    control variates at zero and diverges from the straight run."""
    from fedml_tpu.utils.checkpoint import load_checkpoint, save_checkpoint

    data = _data(samples=8)
    cfg = _cfg(batch_size=4, epochs=2, rounds=4, lr=0.1)
    model = create_model("lr", "synthetic", (FEAT,), N_CLASSES)

    straight = ScaffoldAPI(cfg, data, model)
    for r in range(4):
        straight.train_round(r)

    crashed = ScaffoldAPI(cfg, data, model)
    for r in range(2):
        crashed.train_round(r)
    p = str(tmp_path / "ckpt")
    save_checkpoint(
        p, crashed.global_vars, round_idx=2,
        algo_state=crashed.checkpoint_state(),
    )

    resumed = ScaffoldAPI(cfg, data, model)
    loaded_vars, round_idx, _, _, algo_state, _ = load_checkpoint(p)
    from fedml_tpu.utils.checkpoint import restore_like

    resumed.global_vars = restore_like(resumed.global_vars, loaded_vars)
    assert algo_state is not None
    resumed.restore_state(algo_state)
    for r in range(int(round_idx), 4):
        resumed.train_round(r)

    for a, b in zip(
        jax.tree_util.tree_leaves(straight.global_vars),
        jax.tree_util.tree_leaves(resumed.global_vars),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6
        )
    np.testing.assert_allclose(
        np.asarray(straight.c_server["linear"]["kernel"]),
        np.asarray(resumed.c_server["linear"]["kernel"]),
        rtol=1e-6, atol=1e-6,
    )


def test_mesh_scaffold_matches_vmap():
    """DistributedScaffoldAPI (shard_map over a client mesh, replicated
    control store, psum-scattered row updates) == the single-chip
    simulator at the same seed — params, c_server, AND every c_i row.
    Includes a non-divisible cohort (6 clients over 8 shards… padded), so
    the dummy-client zero-delta path is exercised."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from fedml_tpu.parallel import DistributedScaffoldAPI

    data = synthetic_classification(
        num_clients=8, num_classes=N_CLASSES, feat_shape=(FEAT,),
        samples_per_client=16, partition_method="hetero", ragged=False,
        seed=3,
    )
    cfg = RunConfig(
        data=DataConfig(batch_size=4, pad_bucket=1),
        fed=FedConfig(
            client_num_in_total=8, client_num_per_round=6, comm_round=3,
            epochs=2, frequency_of_the_test=10_000,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        model="lr",
    )
    model = create_model("lr", "synthetic", (FEAT,), N_CLASSES)
    sim = ScaffoldAPI(cfg, data, model)
    mesh_api = DistributedScaffoldAPI(cfg, data, model)
    for r in range(cfg.fed.comm_round):
        _, m_sim = sim.train_round(r)
        _, m_mesh = mesh_api.train_round(r)
        np.testing.assert_allclose(
            float(m_sim["loss_sum"]), float(m_mesh["loss_sum"]), rtol=1e-5
        )
    for name, a, b in (
        ("params", sim.global_vars, mesh_api.global_vars),
        ("c_server", sim.c_server, mesh_api.c_server),
        ("c_stack", sim.c_stack, mesh_api.c_stack),
    ):
        for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        ):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-5,
                err_msg=name,
            )


def test_rejects_momentum_and_spills_oversize_store():
    data = _data()
    cfg = dataclasses.replace(
        _cfg(), train=TrainConfig(client_optimizer="sgd", lr=0.1, momentum=0.9)
    )
    model = create_model("lr", "synthetic", (FEAT,), N_CLASSES)
    with pytest.raises(ValueError, match="plain-SGD"):
        ScaffoldAPI(cfg, data, model)

    # past the HBM budget the store SPILLS to disk instead of refusing
    # (round 3 refused here)
    base = _cfg()
    tiny_budget = dataclasses.replace(
        base,
        fed=dataclasses.replace(base.fed, state_budget_bytes=16),
    )
    api = ScaffoldAPI(tiny_budget, data, model)
    assert api._state_mode == "mmap" and api.c_stack is None
    api.train_round(0)  # and it trains


def test_cohort_body_ignores_padding_rows():
    """Advisor r4: the shared cohort body must derive |S| and the Delta-c
    mean from the inclusion mask (num_samples > 0), not the array axis —
    padding the cohort with pad_clients_to dummy rows must leave the
    round's outputs exactly unchanged."""
    from fedml_tpu.algorithms.scaffold import _make_scaffold_cohort_body
    from fedml_tpu.data.base import pad_clients_to

    data = _data()
    cfg = _cfg(rounds=1)
    model = create_model("lr", "synthetic", (FEAT,), N_CLASSES)
    api = ScaffoldAPI(cfg, data, model)
    sampled, _, _ = api._round_plan(0)
    batch = api._round_batch(sampled, 0)
    rng = jax.random.fold_in(api.rng, 1)
    body = jax.jit(
        _make_scaffold_cohort_body(
            model, api.config, "classification", api._client_mode
        )
    )
    c_rows = jax.tree_util.tree_map(
        lambda a: a[np.asarray(sampled)], api.c_stack
    )
    ref = body(
        api.global_vars, api.c_server, c_rows, *api._place_batch(batch, rng)
    )

    extra = 3
    padded = pad_clients_to(batch, batch.num_clients + extra)
    c_rows_pad = jax.tree_util.tree_map(
        lambda a: jnp.pad(a, [(0, extra)] + [(0, 0)] * (a.ndim - 1)), c_rows
    )
    got = body(
        api.global_vars, api.c_server, c_rows_pad,
        *api._place_batch(padded, rng),
    )
    labels = ("global_vars", "c_server", "c_rows", "metrics")
    for name, a, b in zip(labels, ref, got):
        if name == "c_rows":
            b = jax.tree_util.tree_map(lambda x: x[: batch.num_clients], b)
        for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        ):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-6, atol=1e-7,
                err_msg=name,
            )
