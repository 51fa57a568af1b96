"""Memory-mapped federated store (data/mmap_store.py): round math parity
with the in-RAM path, streaming write, and a 10k-client reduced-shape run
(the client-state store for clients >> RAM; ref
benchmark/README.md:57 federates 342,477 StackOverflow clients)."""

import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgAPI, client_sampling
from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.base import FederatedDataset, stack_clients
from fedml_tpu.data.mmap_store import (
    load_mmap_dataset,
    synth_stackoverflow_mmap,
    write_mmap_dataset,
)
from fedml_tpu.models import create_model


def _small_dataset(num_clients=16, seed=0):
    rng = np.random.default_rng(seed)
    sizes = rng.integers(4, 24, num_clients)
    cx = [rng.normal(size=(n, 6)).astype(np.float32) for n in sizes]
    cy = [rng.integers(0, 4, n).astype(np.int32) for n in sizes]
    tx = rng.normal(size=(32, 6)).astype(np.float32)
    ty = rng.integers(0, 4, 32).astype(np.int32)
    return FederatedDataset(
        name="ram", client_x=cx, client_y=cy, test_x=tx, test_y=ty,
        num_classes=4,
    )


def _as_mmap(data: FederatedDataset, path) -> object:
    flat_x = np.concatenate(list(data.client_x), axis=0)
    flat_y = np.concatenate(list(data.client_y), axis=0)
    sizes = data.train_sample_counts

    def gen_chunk(start, n):
        return flat_x[start:start + n], flat_y[start:start + n]

    write_mmap_dataset(
        str(path), sizes, gen_chunk, (data.test_x, data.test_y),
        num_classes=data.num_classes, name="mmapped", chunk_rows=37,
    )
    return load_mmap_dataset(str(path))


def test_mmap_round_batches_match_in_ram(tmp_path):
    ram = _small_dataset()
    mm = _as_mmap(ram, tmp_path / "store")
    assert mm.num_clients == ram.num_clients
    np.testing.assert_array_equal(
        mm.train_sample_counts, ram.train_sample_counts
    )
    sampled = client_sampling(3, ram.num_clients, 6)
    a = stack_clients(ram, sampled, 8, seed=42)
    b = stack_clients(mm, sampled, 8, seed=42)
    np.testing.assert_array_equal(a.x, b.x)
    np.testing.assert_array_equal(a.y, b.y)
    np.testing.assert_array_equal(a.mask, b.mask)
    np.testing.assert_array_equal(a.num_samples, b.num_samples)


def test_mmap_fedavg_rounds_match_in_ram(tmp_path):
    ram = _small_dataset()
    mm = _as_mmap(ram, tmp_path / "store")
    model = create_model("lr", "synthetic", (6,), 4)
    outs = {}
    for name, data in (("ram", ram), ("mmap", mm)):
        cfg = RunConfig(
            data=DataConfig(batch_size=8, device_cache=False),
            fed=FedConfig(
                client_num_in_total=data.num_clients, client_num_per_round=6,
                comm_round=3, epochs=1, frequency_of_the_test=10_000,
            ),
            train=TrainConfig(client_optimizer="sgd", lr=0.1),
            seed=0,
        )
        api = FedAvgAPI(cfg, data, model)
        for r in range(3):
            api.train_round(r)
        outs[name] = api.global_vars
    import jax

    for a, b in zip(
        jax.tree_util.tree_leaves(outs["ram"]),
        jax.tree_util.tree_leaves(outs["mmap"]),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_streaming_write_never_materializes(tmp_path):
    calls = []

    def gen_chunk(start, n):
        calls.append(n)
        r = np.random.default_rng(start)
        return (
            r.normal(size=(n, 3)).astype(np.float32),
            r.integers(0, 2, n).astype(np.int32),
        )

    sizes = [10] * 40  # 400 rows, chunk_rows=64 -> ceil(400/64)=7 chunks
    write_mmap_dataset(
        str(tmp_path / "s"), sizes, gen_chunk,
        (np.zeros((4, 3), np.float32), np.zeros(4, np.int32)),
        num_classes=2, chunk_rows=64,
    )
    assert max(calls) <= 64
    mm = load_mmap_dataset(str(tmp_path / "s"))
    assert mm.total_train_samples() == 400
    assert len(mm.client_x[3]) == 10


@pytest.mark.parametrize("num_clients", [10_000])
def test_10k_clients_reduced_shape(tmp_path, num_clients):
    """10k clients at tiny shapes through the full FedAvgAPI round path
    (CI-scale version of the 100k bench row)."""
    mm = synth_stackoverflow_mmap(
        str(tmp_path / "so"), num_clients=num_clients, mean_samples=8,
        vocab=64, seq_len=6, seed=1,
    )
    assert mm.num_clients == num_clients
    model = create_model("rnn", "stackoverflow", (6,), 64, vocab_size=64)
    cfg = RunConfig(
        data=DataConfig(batch_size=8, pad_bucket=4, device_cache=False),
        fed=FedConfig(
            client_num_in_total=num_clients, client_num_per_round=10,
            comm_round=2, epochs=1, frequency_of_the_test=10_000,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        seed=0,
    )
    api = FedAvgAPI(cfg, mm, model, task="nwp")
    for r in range(2):
        _, m = api.train_round(r)
    assert np.isfinite(float(np.asarray(m["loss_sum"]).sum()))


def test_imagenet_streaming_store(tmp_path):
    """ImageNet streaming loader: metadata scan -> chunked decode into the
    mmap store; round batches match the in-RAM loader's math."""
    from fedml_tpu.data.imagenet import load_imagenet, load_imagenet_streaming

    rng = np.random.default_rng(0)
    root = tmp_path / "imgnet"
    for split, n in (("train", 6), ("val", 2)):
        for cname in ("n01440764", "n01443537"):
            d = root / split / cname
            d.mkdir(parents=True)
            for i in range(n):
                np.save(d / f"img_{i}.npy", rng.random((8, 8, 3)).astype(np.float32))
    stream = load_imagenet_streaming(
        str(root), str(tmp_path / "store"), num_clients=3, image_size=8,
        chunk_rows=5, seed=0,
    )
    ram = load_imagenet(str(root), num_clients=3, image_size=8, seed=0)
    assert stream.num_clients == 3
    # identical partition (same seed/partitioner): shards must match
    for i in range(3):
        np.testing.assert_allclose(
            np.asarray(stream.client_x[i]), ram.client_x[i], atol=1e-6
        )
        np.testing.assert_array_equal(
            np.asarray(stream.client_y[i]), ram.client_y[i]
        )
    # idempotent reload
    again = load_imagenet_streaming(
        str(root), str(tmp_path / "store"), num_clients=3, image_size=8,
    )
    assert again.total_train_samples() == stream.total_train_samples()


# ---------------------------------------------------------------------------
# incremental builder (MmapStoreBuilder): bounded RAM, header rewrite
# ---------------------------------------------------------------------------


def test_builder_bitmatches_bulk_writer(tmp_path):
    """Clients streamed one at a time through the builder produce a store
    byte-identical to the bulk writer's — same files, same loader."""
    from fedml_tpu.data.mmap_store import MmapStoreBuilder

    data = _small_dataset()
    bulk = _as_mmap(data, tmp_path / "bulk")
    b = MmapStoreBuilder(str(tmp_path / "inc"), flush_bytes=1 << 10)
    for x, y in zip(data.client_x, data.client_y):
        b.add_client(x, y)
    b.finalize((data.test_x, data.test_y), num_classes=4, name="mmapped")
    inc = load_mmap_dataset(str(tmp_path / "inc"))
    assert inc.num_clients == bulk.num_clients
    for i in range(inc.num_clients):
        np.testing.assert_array_equal(
            np.asarray(inc.client_x[i]), np.asarray(bulk.client_x[i])
        )
        np.testing.assert_array_equal(
            np.asarray(inc.client_y[i]), np.asarray(bulk.client_y[i])
        )
    np.testing.assert_array_equal(inc.test_x, bulk.test_x)


def test_builder_ram_ceiling_and_stats(tmp_path):
    """The buffer never holds more than flush_bytes + one client; stats
    expose the mmap_build/* summary row with real flush counts."""
    from fedml_tpu.data.mmap_store import MmapStoreBuilder

    rng = np.random.default_rng(0)
    ceiling = 4 << 10
    logs = []
    b = MmapStoreBuilder(
        str(tmp_path / "s"), flush_bytes=ceiling, log_fn=logs.append
    )
    client_bytes = []
    for _ in range(64):
        n = int(rng.integers(4, 12))
        x = rng.normal(size=(n, 6)).astype(np.float32)
        y = rng.integers(0, 4, n).astype(np.int32)
        client_bytes.append(x.nbytes + y.nbytes)
        b.add_client(x, y)
    b.finalize(
        (np.zeros((4, 6), np.float32), np.zeros(4, np.int32)), num_classes=4
    )
    stats = b.stats()
    assert stats["mmap_build/clients"] == 64
    assert stats["mmap_build/flushes"] >= 2
    assert stats["mmap_build/peak_buffer_bytes"] <= ceiling + max(client_bytes)
    assert stats["mmap_build/rows"] == load_mmap_dataset(
        str(tmp_path / "s")
    ).total_train_samples()
    assert stats["mmap_build/bytes"] > 0 and stats["mmap_build/seconds"] >= 0
    # progress strings while flushing + the final stats row
    assert any(isinstance(m, str) and "mmap build" in m for m in logs)
    assert any(isinstance(m, dict) and "mmap_build/rows" in m for m in logs)


def test_builder_rejects_drift_and_reuse(tmp_path):
    from fedml_tpu.data.mmap_store import MmapStoreBuilder

    b = MmapStoreBuilder(str(tmp_path / "s"))
    b.add_client(np.zeros((3, 6), np.float32), np.zeros(3, np.int32))
    with pytest.raises(ValueError, match="drift"):
        b.add_client(np.zeros((3, 5), np.float32), np.zeros(3, np.int32))
    with pytest.raises(ValueError, match="misaligned"):
        b.add_client(np.zeros((3, 6), np.float32), np.zeros(2, np.int32))
    b.finalize((np.zeros((2, 6), np.float32), np.zeros(2, np.int32)), 4)
    with pytest.raises(RuntimeError, match="finalized"):
        b.add_client(np.zeros((3, 6), np.float32), np.zeros(3, np.int32))


def test_builder_store_trains_identically_to_ram(tmp_path):
    """End-to-end: a builder-written store drives the same FedAvg rounds
    as the in-RAM dataset (the loader-parity contract real-format
    loaders rely on)."""
    from fedml_tpu.data.mmap_store import MmapStoreBuilder

    data = _small_dataset()
    b = MmapStoreBuilder(str(tmp_path / "inc"), flush_bytes=1 << 10)
    for x, y in zip(data.client_x, data.client_y):
        b.add_client(x, y)
    b.finalize((data.test_x, data.test_y), num_classes=4, name="ram")
    mm = load_mmap_dataset(str(tmp_path / "inc"))
    cfg = RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(
            client_num_in_total=16, client_num_per_round=4, comm_round=3,
            epochs=1, frequency_of_the_test=100,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        seed=0,
    )
    model = create_model("lr", "synthetic", (6,), 4)
    ram_api = FedAvgAPI(cfg, data, model)
    ram_api.train()
    mm_api = FedAvgAPI(cfg, mm, model)
    mm_api.train()
    for ra, rb in zip(ram_api.history, mm_api.history):
        assert ra["Train/Loss"] == rb["Train/Loss"]
