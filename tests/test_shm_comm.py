"""Shared-memory local transport (TRPC-equivalent backend, ref
fedml_core/distributed/communication/trpc/trpc_comm_manager.py:25-114):
one-copy send / zero-copy receive semantics, echo over the Observer contract,
federation==simulator oracle, and a latency sweep mirroring the reference's
inline TRPC benchmark (trpc_comm_manager.py:146-211)."""

import tempfile
import threading
import time

import numpy as np
import pytest

from fedml_tpu.core.comm import Observer
from fedml_tpu.core.message import Message
from fedml_tpu.core.shm_comm import ShmCommManager


def test_wire_parts_and_write_into():
    m = Message("t", 1, 2)
    arr = np.arange(20, dtype=np.float32).reshape(4, 5)
    m.add_params("w", arr)
    m.add_params("n", 7)
    size = m.wire_size()
    buf = bytearray(size)
    assert m.write_into(buf) == size
    out = Message.from_bytes(bytes(buf))
    np.testing.assert_array_equal(out.get("w"), arr)
    assert out.get("n") == 7


def test_from_bytes_zero_copy_aliases_buffer():
    m = Message("t", 0, 1)
    m.add_params("w", np.zeros(8, dtype=np.float32))
    buf = bytearray(m.wire_size())
    m.write_into(buf)
    out = Message.from_bytes(buf, copy=False)
    w = out.get("w")
    assert not w.flags.owndata  # aliases, does not own
    # mutating the underlying buffer is visible through the array
    one = np.float32(1.0).tobytes()
    tail = len(buf) - 4
    buf[tail : tail + 4] = one
    assert w[-1] == 1.0
    # copy=True must NOT alias
    out2 = Message.from_bytes(buf, copy=True)
    w2 = out2.get("w")
    buf[tail : tail + 4] = np.float32(2.0).tobytes()
    assert w2[-1] == 1.0


class _Collect(Observer):
    def __init__(self):
        self.got = []
        self.event = threading.Event()

    def receive_message(self, msg_type, msg):
        # copy out: zero-copy arrays are valid only inside the callback
        self.got.append((msg_type, {k: np.array(v) if isinstance(v, np.ndarray) else v
                                    for k, v in msg.params.items()}))
        self.event.set()


@pytest.mark.parametrize("zero_copy", [False, True])
def test_shm_echo(zero_copy):
    with tempfile.TemporaryDirectory() as d:
        a = ShmCommManager(0, d, zero_copy=zero_copy)
        b = ShmCommManager(1, d, zero_copy=zero_copy)
        obs = _Collect()
        b.add_observer(obs)
        t = threading.Thread(target=b.handle_receive_message, daemon=True)
        t.start()
        msg = Message("ping", 0, 1)
        payload = np.random.default_rng(0).normal(size=(64, 64)).astype(np.float32)
        msg.add_params("w", payload)
        msg.add_params("round", 5)
        a.send_message(msg)
        assert obs.event.wait(10)
        kind, params = obs.got[0]
        assert kind == "ping"
        np.testing.assert_array_equal(params["w"], payload)
        assert params["round"] == 5
        b.stop_receive_message()
        a.stop_receive_message()
        t.join(timeout=10)
        assert not t.is_alive()


def test_shm_refuses_a_socket_path_too_long_for_its_address(tmp_path):
    """A ``sock_dir`` whose socket names do not fit a UNIX socket address is
    refused by name, with the length and the limit, before ``bind`` can
    answer with a bare ``OSError``; the longest path that fits is taken."""
    from fedml_tpu.core.shm_comm import _SUN_PATH_MAX, _addr

    deep = tmp_path / ("d" * 120)
    deep.mkdir()
    addr = _addr(str(deep), 0, "ns")
    with pytest.raises(ValueError) as e:
        ShmCommManager(0, str(deep), namespace="ns")
    assert addr in str(e.value)
    assert f"{len(addr)} bytes" in str(e.value) and str(_SUN_PATH_MAX) in str(e.value)
    with tempfile.TemporaryDirectory(prefix="fs", dir="/tmp") as d:
        pad = _SUN_PATH_MAX - len(_addr(d, 0, ""))
        fits = ShmCommManager(0, d, namespace="n" * (pad - 1))
        fits.stop_receive_message()
        with pytest.raises(ValueError, match="bytes"):
            ShmCommManager(0, d, namespace="n" * pad)


def test_shm_handler_exception_not_masked():
    """A raising observer must propagate its own exception (not BufferError
    from closing a still-referenced segment) and must not leak the segment."""

    class _Boom(Observer):
        def receive_message(self, msg_type, msg):
            raise KeyError("no handler for " + msg_type)

    with tempfile.TemporaryDirectory() as d:
        a = ShmCommManager(0, d)
        b = ShmCommManager(1, d, zero_copy=True)
        b.add_observer(_Boom())
        errs = []

        def loop():
            try:
                b.handle_receive_message()
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        t = threading.Thread(target=loop, daemon=True)
        t.start()
        a.send_message(Message("mystery", 0, 1).add_params("w", np.ones(4)))
        t.join(timeout=10)
        assert not t.is_alive()
        assert len(errs) == 1 and isinstance(errs[0], KeyError)
        a.stop_receive_message()
        b.stop_receive_message()


def test_shm_federation_matches_simulator():
    import jax

    from fedml_tpu.algorithms import FedAvgAPI
    from fedml_tpu.algorithms.fedavg_transport import run_shm_federation
    from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
    from fedml_tpu.data.synthetic import synthetic_classification
    from fedml_tpu.models import ModelDef
    from fedml_tpu.models.linear import LogisticRegression

    data = synthetic_classification(
        num_clients=4, num_classes=3, feat_shape=(5,), samples_per_client=12,
        partition_method="homo", seed=9,
    )
    model_def = lambda: ModelDef(
        module=LogisticRegression(num_classes=3), input_shape=(5,),
        num_classes=3, name="lr",
    )
    cfg = RunConfig(
        data=DataConfig(batch_size=-1),
        fed=FedConfig(
            client_num_in_total=4, client_num_per_round=4, comm_round=3,
            epochs=1, frequency_of_the_test=3,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        seed=0,
    )
    sim = FedAvgAPI(cfg, data, model_def())
    sim.train()

    server = run_shm_federation(cfg, data, model_def())
    assert server.round_idx == 3
    for a, b in zip(
        jax.tree_util.tree_leaves(sim.global_vars),
        jax.tree_util.tree_leaves(server.global_vars),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5)


def test_shm_latency_sweep():
    """Parity with the reference's inline TRPC benchmark
    (trpc_comm_manager.py:146-211): round-trip a sweep of tensor sizes;
    assert sanity (finite, monotone-ish in payload), not absolute numbers."""
    with tempfile.TemporaryDirectory() as d:
        a = ShmCommManager(0, d)
        b = ShmCommManager(1, d, zero_copy=True)
        obs = _Collect()
        b.add_observer(obs)
        t = threading.Thread(target=b.handle_receive_message, daemon=True)
        t.start()
        stats = {}
        for n in (1_000, 1_000_000):
            payload = np.ones(n, dtype=np.float32)
            t0 = time.perf_counter()
            reps = 5
            for _ in range(reps):
                obs.event.clear()
                msg = Message("bench", 0, 1).add_params("w", payload)
                a.send_message(msg)
                assert obs.event.wait(10)
            stats[n] = (time.perf_counter() - t0) / reps
        b.stop_receive_message()
        a.stop_receive_message()
        t.join(timeout=10)
        assert all(v > 0 and np.isfinite(v) for v in stats.values())
        # gross sanity only — absolute latency is CI-load-dependent
        assert stats[1_000_000] < 2.0
