"""Fleet client — the OS-process wire client the launcher preforks.

This module is the forkserver preload target (fleet/launcher.py): the
forkserver parent imports it ONCE — paying the jax / transport import
cost once — and every one of the ≥1000 fleet children is a cheap fork of
that warm parent instead of a cold ``python -m fedml_tpu`` interpreter.

A fleet child is a REAL wire client: it builds its own
:class:`~fedml_tpu.core.grpc_comm.GrpcCommManager` on ``base_port +
rank``, dials the tenant's rank-0 endpoint, and runs the stock manager
FSM — :class:`~fedml_tpu.algorithms.fedbuff.FedBuffClientManager`
(entering through the C2S_JOIN admission door, leaving through C2S_LEAVE
when its seeded churn budget is spent) or
:class:`~fedml_tpu.algorithms.fedavg_transport.FedAvgClientManager`
(fixed sync fleet). Faults come from the same per-process
:class:`~fedml_tpu.scheduler.faults.FaultInjector` the CLI wire path
uses; every injected event is captured by a tiny health shim
(:class:`FaultEventLog`) and shipped back in the child's result file so
the launcher can merge a fleet-wide
:class:`~fedml_tpu.scheduler.faults.FaultTrace`.

``LiteTrainer`` replaces the jitted local-train program with a
numpy-only pseudo-update, deterministic in (seed, client, round): a
fleet child exercises the WIRE (join/dispatch/upload/leave, retries,
chaos, backpressure) without ever initializing a jax backend — which is
what makes a 1000-process fleet feasible on one host.

Exit codes (collected by the launcher):
    0  completed (ran until the server's FINISH, after doing work)
    10 left (spent its churn budget, left through the admission door)
    11 finished early (FINISH before any assignment: refused at the
       admission door, or joined a tenant that was already done)
    12 orphaned (server unreachable past the deadman deadline)
    13 error
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

EXIT_COMPLETED = 0
EXIT_LEFT = 10
EXIT_FINISHED_EARLY = 11
EXIT_ORPHANED = 12
EXIT_ERROR = 13

#: test hook — ranks listed here (comma-separated) hang instead of
#: running, simulating a zombie client the launcher must reap at its
#: kill deadline. Never set outside tests.
HANG_ENV = "FLEET_TEST_HANG_RANKS"


class FaultEventLog:
    """Duck-typed stand-in for the server's ClientHealthRegistry on the
    injector's ``health`` slot: records every injected fault event as a
    plain row so the child can ship it home for the launcher's
    fleet-wide FaultTrace merge (O(events injected), bounded by the
    child's own lifetime)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.events: List[list] = []

    def observe_fault(
        self, client_id: int, round_idx: int, kind: str, detail: float = 0.0
    ) -> None:
        with self._lock:
            self.events.append(
                [int(client_id), int(round_idx), str(kind), float(detail)]
            )

    def rows(self) -> List[list]:
        with self._lock:
            return [list(e) for e in self.events]


class LiteTrainer:
    """Numpy-only trainer with the LocalTrainer protocol
    (``update_dataset`` / ``train`` / ``client_index`` / ``last_loss``):
    the pseudo-update perturbs every float leaf deterministically in
    (seed, client, round), so uploads are real model-shaped payloads and
    two runs of the same fleet upload identical bytes — without a jax
    backend, a dataset, or a compile anywhere in the child."""

    def __init__(self, seed: int = 0, lr: float = 0.05):
        self.seed = int(seed)
        self.lr = float(lr)
        self.client_index = 0
        self.last_loss: Optional[float] = None

    def update_dataset(self, client_index) -> None:
        self.client_index = int(client_index or 0)

    def train(self, round_idx, variables: dict) -> Tuple[dict, int]:
        rng = np.random.default_rng([
            self.seed & 0x7FFFFFFF,
            int(self.client_index),
            int(round_idx) & 0x7FFFFFFF,
        ])

        def _step(leaf):
            a = np.asarray(leaf)
            if np.issubdtype(a.dtype, np.floating):
                noise = rng.standard_normal(a.shape).astype(a.dtype)
                return a - np.asarray(self.lr, a.dtype) * noise
            return a

        out = _tree_map(_step, variables)
        self.last_loss = float(rng.random())
        return out, 8


def _tree_map(fn, tree):
    """Minimal pytree map over dict/list/tuple containers, visiting dict
    keys in sorted order (jax's convention) — keeps the child free of
    any jax dependency at train time."""
    if isinstance(tree, dict):
        return {k: _tree_map(fn, tree[k]) for k in sorted(tree)}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_tree_map(fn, v) for v in tree)
    return fn(tree)


def _client_config(payload: dict):
    from fedml_tpu.config import (
        CommConfig,
        DataConfig,
        FedConfig,
        RunConfig,
        TrainConfig,
    )

    return RunConfig(
        data=DataConfig(batch_size=int(payload.get("batch_size", 8))),
        fed=FedConfig(
            client_num_in_total=int(payload["population"]),
            client_num_per_round=int(
                payload.get("client_num_per_round", payload["population"])
            ),
            comm_round=int(payload["rounds"]),
            async_buffer_k=int(payload.get("async_buffer_k", 4)),
            fault_plan=str(payload.get("fault_plan", "")),
            deadline_s=float(payload.get("deadline_s", 0.0)),
            min_clients=int(payload.get("min_clients", 1)),
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        comm=CommConfig(
            send_retries=int(payload.get("send_retries", 6)),
            send_timeout_s=float(payload.get("send_timeout_s", 20.0)),
            send_fault_p=float(payload.get("send_fault_p", 0.0)),
            beacons=bool(payload.get("beacons", True)),
        ),
        seed=int(payload.get("seed", 0)),
    )


def _make_comm(payload: dict, config):
    from fedml_tpu.core.grpc_comm import GrpcCommManager

    rank = int(payload["rank"])
    # the child only ever dials rank 0; expected_peers=2 keeps its own
    # (unused) inbound executor at the floor instead of fleet-sized
    return GrpcCommManager(
        rank,
        {0: "127.0.0.1", rank: "127.0.0.1"},
        base_port=int(payload["base_port"]),
        send_timeout_s=config.comm.send_timeout_s,
        expected_peers=2,
    )


def run_fleet_client(payload: dict) -> Tuple[int, dict]:
    """Run one fleet client to completion in THIS process. Returns
    ``(exit_code, result_row)`` — importable directly by tests (no fork
    required) and by :func:`client_process_main` (the forkserver entry)."""
    from fedml_tpu.scheduler.faults import FaultInjector

    rank = int(payload["rank"])
    config = _client_config(payload)
    events = FaultEventLog()
    injector = FaultInjector.from_config(config, health=events)
    comm = _make_comm(payload, config)
    t0 = time.perf_counter()
    if payload.get("algorithm", "fedbuff") == "fedbuff":
        code, extra = _run_fedbuff(payload, config, comm, injector)
    else:
        code, extra = _run_sync(payload, config, comm, injector)
    result = {
        "rank": rank,
        "exit": code,
        "wall_s": round(time.perf_counter() - t0, 4),
        "fault_events": events.rows(),
    }
    result.update(extra)
    return code, result


def _run_fedbuff(payload, config, comm, injector) -> Tuple[int, dict]:
    from fedml_tpu.algorithms.fedbuff import FedBuffClientManager
    from fedml_tpu.core.message import Message, MessageType as MT

    class FleetWorker(FedBuffClientManager):
        """Stock async worker + the seeded churn budget: after
        ``max_assignments`` handled dispatches the NEXT dispatch is
        answered with C2S_LEAVE — the leave half of the fleet's churn
        waves (the launcher back-fills the freed slot)."""

        def __init__(self, *args, max_assignments: int = 0, **kw):
            super().__init__(*args, **kw)
            self.max_assignments = int(max_assignments)
            self.assignments_done = 0

        def _on_model(self, msg):
            if (
                self.max_assignments
                and not self._leave_requested
                and self.assignments_done >= self.max_assignments
            ):
                self.request_leave()
            prev = self._last_handled_tag
            super()._on_model(msg)
            if not self.left and self._last_handled_tag != prev:
                self.assignments_done += 1

    rank = int(payload["rank"])
    worker = FleetWorker(
        config,
        comm,
        rank,
        LiteTrainer(seed=int(payload.get("seed", 0))),
        orphan_deadline_s=float(payload.get("orphan_deadline_s", 60.0)),
        faults=injector,
        max_assignments=int(payload.get("assignment_budget", 0)),
    )
    # the join announcement precedes run(): the reply (a dispatch when
    # admitted, FINISH when refused at max_workers) queues in the inbox
    # and is handled as soon as run() registers handlers — the same
    # ordering FedSession.add_worker uses for in-process elastic joins
    worker.send_message(Message(MT.C2S_JOIN, rank, 0))
    worker.run()
    if worker.left:
        code = EXIT_LEFT
    elif worker.orphaned:
        code = EXIT_ORPHANED
    elif worker.assignments_done == 0:
        code = EXIT_FINISHED_EARLY
    else:
        code = EXIT_COMPLETED
    return code, {"assignments": worker.assignments_done}


def _run_sync(payload, config, comm, injector) -> Tuple[int, dict]:
    from fedml_tpu.algorithms.fedavg_transport import FedAvgClientManager

    client = FedAvgClientManager(
        config,
        comm,
        int(payload["rank"]),
        LiteTrainer(seed=int(payload.get("seed", 0))),
        faults=injector,
    )
    client.run()  # rounds until the server's FINISH
    return EXIT_COMPLETED, {}


def client_process_main(payload: dict, result_path: Optional[str]) -> None:
    """The forkserver child entry: run the client, write the result row
    (atomically — the launcher may be polling), exit with the class
    code. ``os._exit`` on purpose: a fleet child must never run the
    parent's atexit hooks (telemetry writers, exporters)."""
    # One process per chip, and it is never a fleet child: the launcher's
    # tenant (this child's forkserver grandparent) may hold the chip. A
    # child simulates an edge device — pin the CPU platform before
    # anything here could initialise a jax backend.
    import jax

    jax.config.update("jax_platforms", "cpu")
    rank = int(payload["rank"])
    # the launcher threads the env through the payload: children of a
    # long-lived forkserver inherit the FORKSERVER's environment (frozen
    # at its start), so reading os.environ here alone would miss a hook
    # set after the first fleet ran in this interpreter
    hang = str(payload.get("_test_hang", "")) or os.environ.get(HANG_ENV, "")
    if hang and str(rank) in {r for r in hang.split(",") if r}:
        # zombie simulation (tests): never joins, never exits — the
        # launcher's straggler reaper must SIGTERM/SIGKILL us
        time.sleep(3600)
        os._exit(EXIT_ERROR)
    code = EXIT_ERROR
    result: Dict[str, object] = {"rank": rank, "exit": EXIT_ERROR}
    try:
        code, result = run_fleet_client(payload)
    except BaseException as e:  # noqa: BLE001 — the exit code IS the report
        result = {"rank": rank, "exit": EXIT_ERROR, "error": repr(e)}
        code = EXIT_ERROR
    if result_path:
        try:
            tmp = f"{result_path}.tmp"
            with open(tmp, "w") as f:
                json.dump(result, f)
            os.replace(tmp, result_path)
        except OSError:
            pass
    os._exit(code)


# Forkserver warmth: the whole point of preloading this module is that
# the heavy imports below land in the forkserver parent ONCE — every
# child forks with them already in memory instead of paying a cold
# import per process. Import only (no grpc channels/servers, no jax
# backend init): importing is fork-safe, running is not.
from fedml_tpu import config as _warm_config  # noqa: E402,F401
from fedml_tpu.algorithms import fedavg_transport as _warm_sync  # noqa: E402,F401
from fedml_tpu.algorithms import fedbuff as _warm_fedbuff  # noqa: E402,F401
from fedml_tpu.core import grpc_comm as _warm_grpc  # noqa: E402,F401
from fedml_tpu.core import message as _warm_message  # noqa: E402,F401
from fedml_tpu.scheduler import faults as _warm_faults  # noqa: E402,F401
