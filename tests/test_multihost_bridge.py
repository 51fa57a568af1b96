"""Cross-process multihost: the DCN story
must cross a REAL OS process boundary.

Two pins:
1. the jax.distributed-on-CPU blocker — the coordination service forms
   the process group but this build's CPU PJRT client never federates
   the device topology. Pinned so that an environment upgrade that fixes
   it fails this test LOUDLY (then parallel/multihost.initialize_multihost
   opens the native path and the pin gets retired);
2. the working alternative — a two-process gRPC-bridged hierarchical
   federation (parallel/hierarchical_bridge.py) whose final global model
   EQUALS the in-process HierarchicalFedAvgAPI simulator at the same
   seed: the bridge runs the simulator's own _group_round per process,
   so this is an equality contract, not a smoke test."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port(span: int = 1) -> int:
    """A port N with N..N+span-1 all currently bindable (GrpcCommManager
    binds base_port + rank, so the bridge needs a free PAIR). Close-then-
    reuse race is acceptable for CI; hardcoded ports collide with
    lingering subprocesses of a previous run, which is worse."""
    import socket

    for _ in range(64):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            base = s.getsockname()[1]
        try:
            socks = []
            for off in range(span):
                t = socket.socket()
                t.bind(("127.0.0.1", base + off))
                socks.append(t)
            return base
        except OSError:
            continue
        finally:
            for t in socks:
                t.close()
    raise RuntimeError("no free port span found")


@pytest.mark.slow
def test_jax_distributed_cpu_blocker_is_pinned(tmp_path):
    """Documents (and watches) the backend blocker: np=2 at the
    coordination layer, device_count=1 at the PJRT layer."""
    probe = textwrap.dedent(
        """
        import os, sys, json
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        rank, port = int(sys.argv[1]), sys.argv[2]
        jax.distributed.initialize(
            coordinator_address=f"127.0.0.1:{port}",
            num_processes=2, process_id=rank)
        from jax._src import distributed
        print(json.dumps({
            "rank": rank,
            "coord_np": distributed.global_state.num_processes,
            "device_count": jax.device_count(),
            "process_count": jax.process_count(),
        }))
        """
    )
    script = tmp_path / "probe.py"
    script.write_text(probe)
    port = str(_free_port())
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(rank), port],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        )
        for rank in (0, 1)
    ]
    rows = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=120)
            assert p.returncode == 0, out[-500:]
            rows.append(json.loads(
                [l for l in out.splitlines() if l.startswith("{")][-1]
            ))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for row in rows:
        # the coordination layer DOES form the 2-process group…
        assert row["coord_np"] == 2, row
        # …and the device layer does NOT federate — THE pinned blocker.
        # If this assertion ever fails (device_count == 8), the real
        # jax.distributed multihost path has opened on this image:
        # retire this pin and wire initialize_multihost into CI.
        assert row["device_count"] == 1, (
            "jax.distributed CPU device federation now WORKS — retire "
            f"this blocker pin and enable the native path: {row}"
        )


_DRIVER = """
import os, sys, json
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
# match the pytest conftest's PRNG flavor — the oracle equality below
# compares against a simulator running under it
jax.config.update("jax_threefry_partitionable", True)
import numpy as np
from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
from fedml_tpu.data.synthetic import synthetic_classification
from fedml_tpu.models import create_model
from fedml_tpu.parallel.hierarchical_bridge import run_hierarchical_grpc_group

rank, port, outdir = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
cfg = RunConfig(
    data=DataConfig(batch_size=8),
    fed=FedConfig(client_num_in_total=8, client_num_per_round=6,
                  comm_round=3, epochs=1, group_num=2, group_comm_round=2,
                  frequency_of_the_test=10_000),
    train=TrainConfig(client_optimizer="sgd", lr=0.1),
    seed=0,
)
data = synthetic_classification(num_clients=8, num_classes=3, feat_shape=(6,),
                                samples_per_client=16, partition_method="homo",
                                ragged=False, seed=0)
model = create_model("lr", "synthetic", (6,), 3)
api = run_hierarchical_grpc_group(cfg, data, model, rank, base_port=port,
                                  log_fn=lambda r: print(json.dumps(r), flush=True))
import jax
leaves = [np.asarray(l) for l in jax.tree_util.tree_leaves(api.global_vars)]
np.savez(os.path.join(outdir, f"final_{rank}.npz"),
         **{str(i): l for i, l in enumerate(leaves)})
print("DONE", rank, flush=True)
"""


@pytest.mark.slow
def test_two_process_grpc_bridged_hierarchical_equals_simulator(tmp_path):
    import jax

    script = tmp_path / "driver.py"
    script.write_text(_DRIVER)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # SAME virtual-device config as the in-pytest simulator (conftest):
    # XLA:CPU partitions intra-op work per device count, so a 1-device
    # subprocess would differ from the 8-device simulator at ~1e-4 —
    # the equality contract below needs identical backend config
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    port = str(_free_port(span=2))  # base_port + rank for ranks 0 and 1
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(rank), port, str(tmp_path)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=REPO,
        )
        for rank in (1, 0)
    ]
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            assert p.returncode == 0, out[-1500:]
            assert "DONE" in out
    finally:
        for p in procs:  # a hung rank must not outlive the test
            if p.poll() is None:
                p.kill()
                p.wait()
    finals = [
        np.load(tmp_path / f"final_{rank}.npz") for rank in (0, 1)
    ]
    # both processes ended on the SAME global model
    for k in finals[0].files:
        np.testing.assert_array_equal(finals[0][k], finals[1][k])

    # …and that model equals the in-process simulator's (same seed, same
    # _group_round math — equality, not similarity). NOTE: this config
    # block must mirror _DRIVER's verbatim — drift here shows up as a
    # bridge/simulator mismatch, so check both when touching either.
    from fedml_tpu.algorithms.hierarchical import HierarchicalFedAvgAPI
    from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
    from fedml_tpu.data.synthetic import synthetic_classification
    from fedml_tpu.models import create_model

    cfg = RunConfig(
        data=DataConfig(batch_size=8),
        fed=FedConfig(client_num_in_total=8, client_num_per_round=6,
                      comm_round=3, epochs=1, group_num=2, group_comm_round=2,
                      frequency_of_the_test=10_000),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        seed=0,
    )
    data = synthetic_classification(num_clients=8, num_classes=3,
                                    feat_shape=(6,), samples_per_client=16,
                                    partition_method="homo", ragged=False,
                                    seed=0)
    model = create_model("lr", "synthetic", (6,), 3)
    sim = HierarchicalFedAvgAPI(cfg, data, model)
    for r in range(3):
        sim.train_round(r)
    sim_leaves = [
        np.asarray(l) for l in jax.tree_util.tree_leaves(sim.global_vars)
    ]
    # float tolerance, not bitwise: XLA:CPU's intra-op partitioning (and
    # compile-cache provenance) shifts reduction order across process
    # configs at the ~1e-4 level; the cross-RANK equality above stays
    # exact because both ranks run the same binary config
    for i, l in enumerate(sim_leaves):
        np.testing.assert_allclose(
            finals[0][str(i)], l, rtol=2e-3, atol=5e-4
        )
