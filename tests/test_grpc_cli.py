"""True multi-process federation over gRPC through the CLI (ref
main_fedavg_rpc.py + run scripts: one OS process per participant). Spawns
rank 0 (server) + 2 client ranks as subprocesses on localhost and asserts
the server reports the final round."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest


@pytest.mark.slow
def test_multiprocess_grpc_federation(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # single device per process is fine
    base = [
        sys.executable, "-m", "fedml_tpu",
        "--algorithm", "fedavg",
        "--runtime", "grpc",
        "--dataset", "synthetic",
        "--model", "lr",
        "--client_num_in_total", "2",
        "--client_num_per_round", "2",
        "--comm_round", "2",
        "--batch_size", "-1",
        "--frequency_of_the_test", "2",
        "--base_port", "9310",
        "--seed", "5",
    ]
    procs = [
        subprocess.Popen(
            base + ["--rank", str(rank)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            env=env,
            text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for rank in (1, 2, 0)  # clients first, but any order works
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
            assert p.returncode == 0, out[-2000:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    server_out = outs[-1]
    last = [l for l in server_out.splitlines() if l.startswith("{")][-1]
    row = json.loads(last)
    assert row["round"] == 1  # rounds 0..1 completed
    assert "Test/Acc" in row


@pytest.mark.slow
def test_multiprocess_async_grpc_federation(tmp_path):
    """Barrier-free federation across real OS processes over gRPC:
    rank 0 runs the FedBuff server, ranks 1-2 train-on-arrival. The
    server must complete every buffered step and exit 0 — and the
    clients must exit 0 too, even when their LAST upload races the
    server's shutdown (the normal async end-of-run)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    base = [
        sys.executable, "-m", "fedml_tpu",
        "--algorithm", "fedbuff",
        "--runtime", "grpc",
        "--dataset", "synthetic",
        "--model", "lr",
        "--client_num_in_total", "6",
        "--client_num_per_round", "2",
        "--comm_round", "4",
        "--async_buffer_k", "2",
        "--batch_size", "8",
        "--base_port", "9350",
        "--seed", "5",
    ]
    procs = [
        subprocess.Popen(
            base + ["--rank", str(rank)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            env=env,
            text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for rank in (1, 2, 0)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
            assert p.returncode == 0, out[-2000:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    server_out = outs[-1]
    last = [l for l in server_out.splitlines() if l.startswith("{")][-1]
    row = json.loads(last)
    assert row["server_step"] == 4
    assert "staleness_mean" in row


@pytest.mark.slow
def test_grpc_client_killed_mid_round_server_completes_on_quorum(tmp_path):
    """Chaos: one client process is SIGKILLed mid-federation. The server
    must absorb the dead peer (broadcast failures
    tolerated, deadline+quorum closes the round), keep training with the
    survivors, and exit 0 with the final round logged."""
    import signal
    import time

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    base = [
        sys.executable, "-m", "fedml_tpu",
        "--algorithm", "fedavg",
        "--runtime", "grpc",
        "--dataset", "synthetic",
        "--model", "lr",
        "--client_num_in_total", "3",
        "--client_num_per_round", "3",
        "--comm_round", "4",
        "--batch_size", "-1",
        "--frequency_of_the_test", "4",
        "--deadline_s", "2.0",
        "--min_clients", "2",
        "--base_port", "9330",
        "--seed", "5",
    ]
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = {
        rank: subprocess.Popen(
            base + ["--rank", str(rank)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            env=env,
            text=True,
            cwd=cwd,
        )
        for rank in (1, 2, 3, 0)
    }
    import threading

    lines = []
    reader = threading.Thread(
        target=lambda: lines.extend(iter(procs[0].stdout.readline, "")),
        daemon=True,
    )
    reader.start()
    try:
        # wait until round 0 has actually completed (first logged row) so
        # the kill lands mid-federation, not during process startup
        deadline = time.time() + 180
        while time.time() < deadline and not any(
            l.startswith("{") for l in lines
        ):
            assert procs[0].poll() is None, "".join(lines)[-2000:]
            time.sleep(0.5)
        assert any(l.startswith("{") for l in lines), "round 0 never completed"
        procs[3].send_signal(signal.SIGKILL)
        assert procs[0].wait(timeout=240) == 0, "".join(lines)[-2000:]
        reader.join(timeout=10)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
    rows = [json.loads(l) for l in lines if l.startswith("{")]
    assert rows and rows[-1]["round"] == 3  # rounds 0..3 completed
    assert "Test/Acc" in rows[-1]
    assert np.isfinite(rows[-1]["Test/Acc"])
