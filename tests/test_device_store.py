"""Device-resident data store + mixed-precision policy tests."""

import numpy as np
import pytest

from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.base import FederatedDataset, stack_clients
from fedml_tpu.data.device_store import DeviceDataStore
from fedml_tpu.data.synthetic import synthetic_classification


def _data(feat_shape=(6,), num_clients=12, samples_per_client=20):
    return synthetic_classification(
        num_clients=num_clients,
        num_classes=5,
        feat_shape=feat_shape,
        samples_per_client=samples_per_client,
        partition_method="hetero",
        seed=3,
    )


def _tokens():
    """Token documents: int32 rows with a label per position."""
    rng = np.random.default_rng(5)
    sizes = [9, 20, 13, 17, 4, 11]
    docs = [rng.integers(0, 97, size=(n, 17)).astype(np.int32) for n in sizes]
    return FederatedDataset(
        name="tokens",
        client_x=[d[:, :-1] for d in docs],
        client_y=[d[:, 1:] for d in docs],
        test_x=docs[0][:, :-1],
        test_y=docs[0][:, 1:],
        num_classes=97,
    )


def _population(kind):
    if kind == "tokens":
        return _tokens(), [0, 2, 3, 5]
    return _data(feat_shape=kind), [0, 3, 7, 11]


SHAPES = [(6,), (5, 5), (4, 4, 3), "tokens"]


@pytest.mark.parametrize("kind", SHAPES, ids=str)
def test_store_batch_bitmatches_host_stacking(kind):
    """The on-device gather must produce exactly the batch stack_clients
    builds on host (same seed, same bucket contract) — the store is a
    transport optimization, never a math change — whatever a sample's
    shape: the device holds lane-padded rows, the batch has the sample's shape."""
    data, sampled = _population(kind)
    store = DeviceDataStore(data)
    # whole 128-lane rows, zero beyond the sample
    assert store.flat_x.ndim == 2 and store.flat_x.shape[1] % 128 == 0
    width = int(np.prod(store.feat_shape))
    assert not np.asarray(store.flat_x)[:, width:].any()
    assert store.flat_y.ndim == 1 or store.flat_y.shape[1] % 128 == 0
    assert store.feat_shape == data.client_x[0].shape[1:]
    assert store.label_shape == data.client_y[0].shape[1:]
    for seed in (0, 9):
        host = stack_clients(data, sampled, 8, seed=seed, pad_bucket=2)
        dev = store.round_batch(sampled, 8, seed=seed, pad_bucket=2)
        for name in ("x", "y", "mask", "num_samples"):
            got, want = np.asarray(getattr(dev, name)), getattr(host, name)
            assert got.dtype == want.dtype and got.shape == want.shape, name
            np.testing.assert_array_equal(got, want)


def test_no_op_of_the_gather_program_is_the_size_of_the_population():
    """The rule the copy broke: every instruction of the compiled gather
    program is the size of the cohort's batch; only its parameters have
    the population's row count as a leading dimension."""
    import re

    import jax.numpy as jnp

    data = _data(feat_shape=(5, 5), num_clients=12, samples_per_client=83)
    store = DeviceDataStore(data)
    n = store.flat_x.shape[0]
    idx, mask, steps, bs, _ = store.round_indices([0, 3, 7], 8, seed=0)
    assert n == 12 * 83 and n not in idx.shape + (steps, bs, steps * bs)
    text = store.gather_program(steps, bs).lower(
        store.flat_x, store.flat_y, jnp.asarray(idx), jnp.asarray(mask)
    ).compile().as_text()
    sized = [
        line.strip() for line in text.splitlines()
        if re.search(rf"= \(?[a-z0-9]+\[{n}[,\]]", line)
    ]
    assert len(sized) >= 2, text  # flat_x and flat_y, and the fusions' own
    assert all(" parameter(" in line for line in sized), sized


def test_fits_on_device_reckons_lane_padded_rows(monkeypatch):
    """A store is admitted at the size the device will hold it: 60 floats a
    sample take a 128-lane row, over twice their ``nbytes``."""
    from fedml_tpu.data.device_store import fits_on_device

    data = _data(feat_shape=(60,), num_clients=4, samples_per_client=50)
    nbytes = sum(a.nbytes for a in data.client_x + data.client_y)
    held = 200 * (128 * 4 + 4)
    assert nbytes == 200 * (60 * 4 + 4) < held
    monkeypatch.setenv("FEDML_TPU_DEVICE_CACHE_MAX_BYTES", str(held - 1))
    assert not fits_on_device(data)  # nbytes is under the cap, the rows are not
    monkeypatch.setenv("FEDML_TPU_DEVICE_CACHE_MAX_BYTES", str(held))
    assert fits_on_device(data)


def test_fedavg_store_matches_host_path():
    """A FedAvg run with device_cache on == the same run with it off."""
    import jax

    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.models import create_model

    data = _data()
    model = create_model("lr", "synthetic", (6,), 5)
    rows = {}
    for cache in (True, False):
        cfg = RunConfig(
            data=DataConfig(batch_size=8, device_cache=cache),
            fed=FedConfig(
                client_num_in_total=12, client_num_per_round=4, comm_round=3
            ),
            train=TrainConfig(lr=0.1),
            model="lr",
        )
        api = FedAvgAPI(cfg, data, model)
        assert (api._store is not None) == cache
        for r in range(3):
            api.train_round(r)
        rows[cache] = api.global_vars
    for a, b in zip(
        jax.tree_util.tree_leaves(rows[True]), jax.tree_util.tree_leaves(rows[False])
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_bf16_compute_dtype_learns_and_keeps_fp32_master():
    """bfloat16 compute policy: params stay fp32 (master weights), the model
    still reaches the same accuracy band as fp32 on an easy problem."""
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.models import create_model

    data = _data()
    model = create_model("lr", "synthetic", (6,), 5)
    accs = {}
    for dt in ("float32", "bfloat16"):
        cfg = RunConfig(
            data=DataConfig(batch_size=8),
            fed=FedConfig(
                client_num_in_total=12, client_num_per_round=12, comm_round=25
            ),
            train=TrainConfig(lr=0.2, compute_dtype=dt),
            model="lr",
        )
        api = FedAvgAPI(cfg, data, model)
        for r in range(25):
            api.train_round(r)
        import jax

        for leaf in jax.tree_util.tree_leaves(api.global_vars):
            assert leaf.dtype == jnp.float32  # master weights never degrade
        _, accs[dt] = api.evaluate_global()
    assert accs["bfloat16"] > 0.75
    assert abs(accs["bfloat16"] - accs["float32"]) < 0.1
