"""The metamorphic correctness oracle carried over from the reference's CI
(CI-script-fedavg.sh:42-58): with full batch (batch_size=-1), one local epoch,
and all clients participating, FedAvg must equal centralized full-batch SGD —
because the sample-weighted average of per-client gradients IS the centralized
gradient. Deterministic PRNG + CPU float32 makes this near-exact here (the
reference asserts to 3 decimals via wandb-summary.json)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import ModelDef
from fedml_tpu.models.linear import LogisticRegression
from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.train.losses import masked_softmax_ce


NUM_CLIENTS = 8
NUM_CLASSES = 5
FEAT = (6,)


def _make_data():
    return synthetic_classification(
        num_clients=NUM_CLIENTS,
        num_classes=NUM_CLASSES,
        feat_shape=FEAT,
        samples_per_client=20,
        partition_method="homo",
        ragged=True,
        seed=42,
    )


def _make_model():
    return ModelDef(
        module=LogisticRegression(num_classes=NUM_CLASSES),
        input_shape=FEAT,
        num_classes=NUM_CLASSES,
        name="lr",
    )


def _centralized_sgd(model, data, lr, rounds):
    """Full-batch centralized GD, `rounds` steps."""
    x, y = data.centralized_train()
    x, y = jnp.asarray(x), jnp.asarray(y)
    mask = jnp.ones(x.shape[0])
    variables = model.init(jax.random.fold_in(jax.random.PRNGKey(0), 0))
    params = variables["params"]

    def loss_fn(p):
        logits, _ = model.apply({"params": p}, x, train=True)
        return masked_softmax_ce(logits, y, mask)

    for _ in range(rounds):
        g = jax.grad(loss_fn)(params)
        params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, g)
    return params


@pytest.mark.parametrize("rounds", [1, 5])
def test_federated_equals_centralized(rounds):
    data = _make_data()
    model = _make_model()
    lr = 0.1
    config = RunConfig(
        data=DataConfig(batch_size=-1),
        fed=FedConfig(
            client_num_in_total=NUM_CLIENTS,
            client_num_per_round=NUM_CLIENTS,
            comm_round=rounds,
            epochs=1,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=lr),
        seed=0,
    )
    api = FedAvgAPI(config, data, model)
    api.train()
    fed_params = api.global_vars["params"]
    cen_params = _centralized_sgd(model, data, lr, rounds)
    for a, b in zip(
        jax.tree_util.tree_leaves(fed_params), jax.tree_util.tree_leaves(cen_params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_fedavg_learns_synthetic():
    """End-to-end smoke: accuracy on separable synthetic data improves well
    above chance (ref CI smoke tests, CI-script-fedavg.sh:33-39)."""
    data = _make_data()
    model = _make_model()
    config = RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(
            client_num_in_total=NUM_CLIENTS,
            client_num_per_round=4,
            comm_round=20,
            epochs=2,
            frequency_of_the_test=20,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
    )
    api = FedAvgAPI(config, data, model)
    final = api.train()
    assert final["Test/Acc"] > 0.5


def test_client_sampling_parity():
    """Sampling must match the reference exactly (np.random.seed(round_idx),
    FedAVGAggregator.py:80-88)."""
    from fedml_tpu.algorithms.fedavg import client_sampling

    np.random.seed(3)
    expect = np.random.choice(range(100), 10, replace=False)
    got = client_sampling(3, 100, 10)
    assert np.array_equal(got, expect)
    # full participation returns all clients
    assert np.array_equal(client_sampling(0, 5, 5), np.arange(5))


def test_scan_and_vmap_client_schedules_agree():
    """The two client schedules are THE SAME math executed in different
    orders (scan: one client's full local run at a time, full-size
    matmuls; vmap: all clients batched). The flagship bench row rides the
    scan schedule for its MXU tiling (0.77 vs 0.42 device MFU on the
    transformer LM when last recorded), so their numerical agreement is a
    load-bearing contract, not an implementation detail."""
    import dataclasses

    import jax
    import numpy as np

    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
    from fedml_tpu.data.synthetic import synthetic_classification
    from fedml_tpu.models import create_model

    data = synthetic_classification(
        num_clients=8, num_classes=3, feat_shape=(6,), samples_per_client=16,
        partition_method="hetero", ragged=False, seed=0,
    )
    model = create_model("lr", "synthetic", (6,), 3)
    base = RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(
            client_num_in_total=8, client_num_per_round=5, comm_round=3,
            epochs=2, frequency_of_the_test=10_000,
        ),
        train=TrainConfig(client_optimizer="adam", lr=0.05),
        seed=0,
    )
    apis = {}
    for sched in ("vmap", "scan"):
        cfg = dataclasses.replace(
            base, fed=dataclasses.replace(base.fed, client_parallelism=sched)
        )
        api = FedAvgAPI(cfg, data, model)
        assert api._client_mode == sched
        for r in range(3):
            api.train_round(r)
        apis[sched] = api
    for a, b in zip(
        jax.tree_util.tree_leaves(apis["vmap"].global_vars),
        jax.tree_util.tree_leaves(apis["scan"].global_vars),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )
