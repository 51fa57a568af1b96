"""FleetLauncher — supervise ≥1000 OS-process wire clients against one tenant.

The launcher owns both halves of a wire fleet:

- **the tenant**: one server-only :class:`~fedml_tpu.serve.session.FedSession`
  (``external_clients=True``) hosted in a
  :class:`~fedml_tpu.serve.server.FederationServer` in THIS process, its
  rank-0 gRPC endpoint sized by the spec's connection budgets
  (``grpc_max_workers`` / ``grpc_stream_budget``);
- **the fleet**: client OS processes preforked through a ``forkserver``
  context (fleet/client.py is the preload target, so ≥1000 children fork
  from one warm parent instead of paying 1000 cold jax/grpc imports).

The churn loop IS the rolling population: the spec's seed-deterministic
``join_order()`` feeds a spawn queue; at most ``max_live`` children run
concurrently; every reaped exit (a client left after spending its
``assignment_budget``, was refused at the admission door, or completed)
frees a slot that is back-filled from the queue. Join/leave waves at
fleet scale therefore reduce to bounded process supervision:

- O(active) state: per-child result files are folded into aggregate
  counters and deleted as children are reaped; the event log is a
  bounded deque — nothing the launcher keeps grows with the total
  population.
- stragglers/zombies: each child gets a kill deadline
  (``client_deadline_s``); past it the launcher escalates SIGTERM →
  SIGKILL and counts the reap. A whole-fleet watchdog
  (``run_deadline_s``) stops the tenant and fails the run rather than
  hang CI.
- the server thread bound is ASSERTED, not eyeballed: the launcher
  samples the live ``grpc-comm`` executor threads and fails the run if
  they ever exceed the configured executor size.

Launcher stats stream into the process-global
:class:`~fedml_tpu.telemetry.wire.FleetAggregator` (``/fleet`` when the
server has an ops port) and land in ``fleet_stats.json`` next to the
merged fleet-wide ``fault_trace.json`` — which replays byte-identically
through ``fault_plan="trace:<path>"`` on a spec with the same seed.

``mode="cli"`` drives full ``python -m fedml_tpu --rank N`` processes
through the same supervision loop — one code path for the 8-rank CI
parity smoke and the 1000-process lite fleet.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from fedml_tpu.fleet.client import (
    EXIT_COMPLETED,
    EXIT_ERROR,
    EXIT_FINISHED_EARLY,
    EXIT_LEFT,
    EXIT_ORPHANED,
    HANG_ENV,
    client_process_main,
)
from fedml_tpu.fleet.spec import FleetSpec

_EXIT_CLASS = {
    EXIT_COMPLETED: "completed",
    EXIT_LEFT: "left",
    EXIT_FINISHED_EARLY: "finished_early",
    EXIT_ORPHANED: "orphaned",
    EXIT_ERROR: "errors",
}

#: grace after the tenant finishes before leftover children are
#: terminated — long enough for the FINISH broadcast to reach them
_FINISH_GRACE_S = 10.0
#: SIGTERM → SIGKILL escalation gap for reaped stragglers
_KILL_GRACE_S = 5.0
#: how long an empty fleet must persist (tenant still not done) before
#: the launcher declares it exhausted — covers the window where clients
#: have exited on FINISH but the server thread is still finalizing
_EXHAUSTED_GRACE_S = 10.0


def _grpc_comm_threads(prefix: str = "grpc-comm") -> int:
    """Live threads of ONE gRPC executor in THIS process, identified by its
    unique ``thread_name_prefix`` (``GrpcCommManager.thread_prefix``). The
    prefix scoping matters: idle executor threads left behind by earlier
    managers in the same process (previous lite-mode runs, test suites)
    must not count against THIS server's thread bound."""
    return sum(
        1 for t in threading.enumerate() if t.name.startswith(prefix)
    )


class FleetLauncher:
    """Materialize a :class:`FleetSpec` and run it to completion."""

    def __init__(
        self,
        spec: FleetSpec,
        out_dir: str,
        log_fn: Optional[Callable[[str], None]] = None,
        prom_port: Optional[int] = None,
    ):
        self.spec = spec
        self.out_dir = str(out_dir)
        self.prom_port = prom_port
        self._log = log_fn or (lambda m: print(f"[fleet] {m}", flush=True))
        self._client_dir = os.path.join(self.out_dir, "clients")
        # bounded event log: O(max_live), NOT O(population)
        self.recent = deque(maxlen=max(32, 4 * spec.max_live))
        self.stats: Dict[str, object] = {}
        self._fault_events: List[list] = []
        self._server_comm = None
        # ranks refused at the admission door go back in the queue (the
        # server admits a refused rank once a slot opens) — bounded
        # per-rank so a saturated tenant can't spin a rank forever
        self._requeue: deque = deque()
        self._requeue_counts: Dict[int, int] = {}
        self._spawn_pause_until = 0.0

    # -- public ------------------------------------------------------------

    def run(self) -> dict:
        os.makedirs(self._client_dir, exist_ok=True)
        t0 = time.monotonic()
        self.stats = {
            "population": self.spec.population,
            "max_live": self.spec.max_live,
            "algorithm": self.spec.algorithm,
            "mode": self.spec.mode,
            "spawned": 0,
            "completed": 0,
            "left": 0,
            "finished_early": 0,
            "orphaned": 0,
            "errors": 0,
            "reaped": 0,
            "terminated_late": 0,
            "no_result": 0,
            "never_spawned": 0,
            "fault_events": 0,
            "grpc_threads_max": 0,
            "ok": False,
        }
        try:
            if self.spec.mode == "cli":
                self._run_cli()
            else:
                self._run_lite()
        finally:
            self.stats["elapsed_s"] = round(time.monotonic() - t0, 3)
            elapsed = max(1e-9, float(self.stats["elapsed_s"]))
            joined = self.stats.get(
                "joins_accepted", self.stats["spawned"]
            )
            self.stats["joined_per_s"] = round(float(joined) / elapsed, 3)
            self._publish_stats(final=True)
            with open(os.path.join(self.out_dir, "fleet_stats.json"), "w") as f:
                json.dump(self.stats, f, indent=2, sort_keys=True)
            if self.spec.mode == "lite":
                # the server ran in THIS process, so the fleet digests
                # (per-tier train_s/rtt_s percentiles fed by client
                # beacons) are in the process-global aggregator — persist
                # them so out-of-process consumers (CI) can read
                # latency percentiles without scraping the /fleet route.
                # cli-mode servers own their aggregator and publish it via
                # their own ops port instead.
                try:
                    from fedml_tpu.telemetry.wire import get_fleet

                    path = os.path.join(self.out_dir, "fleet_telemetry.json")
                    with open(path, "w") as f:
                        json.dump(
                            get_fleet().snapshot(), f,
                            indent=2, sort_keys=True,
                        )
                except Exception:  # noqa: BLE001 — telemetry must not fail the run
                    pass
        return dict(self.stats)

    # -- lite mode (forkserver fleet against an in-process tenant) ---------

    def _run_lite(self) -> None:
        import multiprocessing as mp

        server, session = self._build_tenant()
        ctx = mp.get_context("forkserver")
        try:
            # warm parent: all children fork from one process that has
            # already paid the jax/grpc/fedml imports (fleet/client.py)
            ctx.set_forkserver_preload(["fedml_tpu.fleet.client"])
        except Exception:  # noqa: BLE001 — forkserver already running
            pass
        sync = self.spec.algorithm == "fedavg"
        pending = deque(
            self.spec.client_ranks() if sync else self.spec.join_order()
        )
        live: Dict[int, dict] = {}
        try:
            if sync:
                # the sync INIT broadcast blocks until every wire rank
                # answers — the whole fixed fleet must exist first
                while pending:
                    self._spawn(ctx, pending.popleft(), live)
                server.start([session.name])
            else:
                # fedbuff: the admission door is open from the start;
                # churn waves roll the population through max_live slots
                server.start([session.name])
            self._supervise(ctx, session, pending, live)
            self.stats["never_spawned"] = len(pending)
            try:
                session.wait(timeout=1.0)
            except Exception as e:  # noqa: BLE001 — priced below
                self.stats.setdefault("session_error", repr(e))
            self._collect_session(session)
            self._assert_bounds()
            self.stats["ok"] = (
                session.state == "done"
                and not self.stats.get("session_error")
                and not self.stats.get("watchdog_expired")
                and not self.stats.get("fleet_exhausted")
                and self.stats["errors"] == 0
                and self.stats["orphaned"] == 0
                and self.stats["stuck"] == 0
                and bool(self.stats["thread_bound_ok"])
            )
        finally:
            self._kill_all(live)
            try:
                server.close()
            except Exception:  # noqa: BLE001 — teardown best effort
                pass
        self._write_trace()

    def _build_tenant(self):
        from fedml_tpu.config import (
            CommConfig,
            DataConfig,
            FedConfig,
            RunConfig,
            TrainConfig,
        )
        from fedml_tpu.core.grpc_comm import GrpcCommManager
        from fedml_tpu.data.synthetic import synthetic_classification
        from fedml_tpu.models import create_model
        from fedml_tpu.serve.server import FederationServer

        spec = self.spec
        sync = spec.algorithm == "fedavg"
        config = RunConfig(
            data=DataConfig(batch_size=spec.batch_size),
            fed=FedConfig(
                client_num_in_total=spec.population,
                client_num_per_round=(
                    spec.population if sync else spec.max_live
                ),
                comm_round=spec.rounds,
                epochs=1,
                # eval exactly once, at the final flush: every eval runs
                # (and first compiles) inside the server's single drain
                # thread, and a fleet's clients are all waiting on that
                # thread for their upload replies — mid-run evals at
                # fleet scale turn straight into orphan deadlines
                frequency_of_the_test=spec.rounds,
                async_buffer_k=spec.async_buffer_k,
                fault_plan=spec.fault_plan_spec(),
                deadline_s=spec.deadline_s,
            ),
            train=TrainConfig(client_optimizer="sgd", lr=0.1),
            comm=CommConfig(
                send_retries=spec.send_retries,
                send_timeout_s=spec.send_timeout_s,
                grpc_max_workers=spec.grpc_max_workers,
                grpc_stream_budget=spec.grpc_stream_budget,
            ),
            seed=spec.seed,
        )
        data = synthetic_classification(
            num_clients=spec.population,
            num_classes=spec.num_classes,
            feat_shape=(spec.feat_dim,),
            samples_per_client=16,
            partition_method="homo",
            seed=spec.seed + 1,
        )
        model = create_model(
            "lr", "synthetic", (spec.feat_dim,), spec.num_classes
        )
        table = {r: "127.0.0.1" for r in range(spec.population + 1)}

        def comm_factory(rank: int):
            if rank != 0:
                raise RuntimeError(
                    "fleet tenant is server-only; client comms live in the "
                    f"fleet's OS processes (asked for rank {rank})"
                )
            comm = GrpcCommManager(
                0,
                table,
                base_port=spec.base_port,
                send_timeout_s=spec.send_timeout_s,
                max_workers=spec.grpc_max_workers,
                stream_budget=spec.grpc_stream_budget,
                # concurrency is bounded by the wave width, not the
                # total population — auto-size the executor from it
                expected_peers=spec.max_live,
            )
            self._server_comm = comm
            return comm

        server = FederationServer(log_dir=self.out_dir, prom_port=self.prom_port)
        kw: Dict[str, object] = dict(
            algorithm=spec.algorithm,
            runtime="grpc",
            comm_factory=comm_factory,
            external_clients=True,
        )
        if not sync:
            kw["max_workers"] = spec.max_workers
        session = server.create_session("fleet", config, data, model, **kw)
        return server, session

    def _payload(self, rank: int) -> dict:
        spec = self.spec
        return {
            "rank": rank,
            "population": spec.population,
            "client_num_per_round": (
                spec.population if spec.algorithm == "fedavg"
                else spec.max_live
            ),
            "algorithm": spec.algorithm,
            "rounds": spec.rounds,
            "async_buffer_k": spec.async_buffer_k,
            "seed": spec.seed,
            "base_port": spec.base_port,
            "fault_plan": spec.fault_plan_spec(),
            "send_fault_p": spec.send_fault_p,
            "send_retries": spec.send_retries,
            "send_timeout_s": spec.send_timeout_s,
            "deadline_s": spec.deadline_s,
            "orphan_deadline_s": spec.orphan_deadline_s,
            "assignment_budget": spec.assignment_budget(rank),
            "batch_size": spec.batch_size,
            # test hook, threaded through the payload because forkserver
            # children inherit the forkserver's env, not the launcher's
            "_test_hang": os.environ.get(HANG_ENV, ""),
        }

    def _spawn(self, ctx, rank: int, live: Dict[int, dict]) -> None:
        result_path = os.path.join(self._client_dir, f"rank_{rank}.json")
        proc = ctx.Process(
            target=client_process_main,
            args=(self._payload(rank), result_path),
            name=f"fleet-client-{rank}",
            daemon=True,
        )
        proc.start()
        now = time.monotonic()
        live[rank] = {
            "proc": proc,
            "result": result_path,
            "kill_at": now + self.spec.client_deadline_s,
            "term_at": None,
        }
        self.stats["spawned"] = int(self.stats["spawned"]) + 1

    def _supervise(self, ctx, session, pending, live: Dict[int, dict]) -> None:
        """The churn loop: reap, back-fill, enforce deadlines, sample the
        thread bound — until the tenant is done and the fleet is drained."""
        spec = self.spec
        t0 = time.monotonic()
        done_at: Optional[float] = None
        empty_since: Optional[float] = None
        last_pub = 0.0
        while True:
            now = time.monotonic()
            self._reap(live, late=done_at is not None)
            done = session.done
            if done and done_at is None:
                done_at = now
            if not done:
                while self._requeue:
                    pending.append(self._requeue.popleft())
                while (
                    pending
                    and len(live) < spec.max_live
                    and now >= self._spawn_pause_until
                ):
                    self._spawn(ctx, pending.popleft(), live)
            comm = self._server_comm
            if comm is not None:
                self.stats["grpc_threads_max"] = max(
                    int(self.stats["grpc_threads_max"]),
                    _grpc_comm_threads(
                        getattr(comm, "thread_prefix", "grpc-comm")
                    ),
                )
            if now - last_pub >= 1.0:
                last_pub = now
                self.stats["live"] = len(live)
                self._publish_stats()
            if done and not live:
                break
            if not done and not live and not pending:
                # every client has run and exited but the tenant hasn't
                # reported done yet. Grace before declaring the fleet
                # exhausted: at the natural end of a run the clients exit
                # on FINISH while the server thread is still finalizing
                # (final eval, checkpoint, state flip) — stopping the
                # session in that window would misread a clean finish as
                # starvation. Only a tenant still not done after the
                # grace genuinely ran out of assignment supply.
                if empty_since is None:
                    empty_since = now
                elif now - empty_since > _EXHAUSTED_GRACE_S:
                    self.stats["fleet_exhausted"] = True
                    self._log(
                        "fleet exhausted before the tenant finished — "
                        "stopping tenant (raise population/assignments?)"
                    )
                    try:
                        session.stop()
                    except Exception:  # noqa: BLE001 — teardown best effort
                        pass
                    break
            else:
                empty_since = None
            if done and done_at is not None and now - done_at > _FINISH_GRACE_S:
                # the tenant is finished; whatever is still alive missed
                # its FINISH (late joiner, zombie) — reap it now
                for rec in live.values():
                    rec["kill_at"] = min(rec["kill_at"], now)
                done_at = now  # re-arm so escalation gets its grace too
            if now - t0 > spec.run_deadline_s:
                self.stats["watchdog_expired"] = True
                self._log(
                    f"run deadline {spec.run_deadline_s}s expired with "
                    f"{len(live)} live clients — stopping tenant"
                )
                try:
                    session.stop()
                except Exception:  # noqa: BLE001 — teardown best effort
                    pass
                break
            time.sleep(0.05)
        self.stats["stuck"] = len(live)
        self.stats["live"] = len(live)

    def _reap(self, live: Dict[int, dict], late: bool = False) -> None:
        now = time.monotonic()
        for rank in list(live):
            rec = live[rank]
            proc = rec["proc"]
            if not proc.is_alive():
                proc.join(timeout=1.0)
                self._fold(rank, proc.exitcode, rec["result"], late=late)
                del live[rank]
                continue
            if rec["term_at"] is not None:
                if now - rec["term_at"] > _KILL_GRACE_S:
                    proc.kill()  # SIGTERM was ignored — escalate
            elif now > rec["kill_at"]:
                self.stats["reaped"] = int(self.stats["reaped"]) + 1
                self.recent.append((round(now, 1), rank, "reaped"))
                proc.terminate()
                rec["term_at"] = now

    def _fold(self, rank: int, exitcode, result_path: str, late: bool) -> None:
        """Fold one child into the aggregate counters and DELETE its
        result file — launcher state stays O(active)."""
        cls = None
        if exitcode is not None and exitcode < 0:
            cls = "terminated_late" if late else "errors"
        else:
            cls = _EXIT_CLASS.get(int(exitcode or 0), "errors")
        self.stats[cls] = int(self.stats.get(cls, 0)) + 1
        self.recent.append((round(time.monotonic(), 1), rank, cls))
        if cls == "finished_early" and not late:
            # refused at the admission door while the tenant still runs:
            # the rank gets another shot once a slot opens, and the spawn
            # pump backs off briefly so a saturated door doesn't turn
            # into a fork storm of instant refusals. The retry cap only
            # guards against a PERMANENTLY refused rank looping forever —
            # it must sit far above the attempts a saturated door needs,
            # because a rank dropped here never delivers its assignment
            # budget and a fleet sized supply≈demand (the ci gate) would
            # starve the server of its last uploads
            n = self._requeue_counts.get(rank, 0)
            if n < 50:
                self._requeue_counts[rank] = n + 1
                self._requeue.append(rank)
            self._spawn_pause_until = time.monotonic() + 0.25
        try:
            with open(result_path) as f:
                row = json.load(f)
            os.unlink(result_path)
        except (OSError, ValueError):
            self.stats["no_result"] = int(self.stats["no_result"]) + 1
            return
        events = row.get("fault_events") or []
        self._fault_events.extend(events)
        self.stats["fault_events"] = int(self.stats["fault_events"]) + len(
            events
        )
        if row.get("error"):
            # keep ONE exemplar, not a list that grows with the fleet
            self.stats.setdefault("first_client_error", str(row["error"]))

    def _kill_all(self, live: Dict[int, dict]) -> None:
        for rec in live.values():
            try:
                rec["proc"].kill()
            except Exception:  # noqa: BLE001 — teardown best effort
                pass
        for rank in list(live):
            rec = live.pop(rank)
            rec["proc"].join(timeout=2.0)
            self._fold(rank, rec["proc"].exitcode, rec["result"], late=True)

    def _collect_session(self, session) -> None:
        row = session.status()
        for key in (
            "state",
            "server_steps",
            "version",
            "round",
            "joins_accepted",
            "joins_refused",
            "leaves",
            "comm/refused",
            "comm/send_refused",
        ):
            if key in row:
                self.stats[key] = row[key]

    def _assert_bounds(self) -> None:
        """The thread bound is a hard assertion of the fleet gate: the
        rank-0 executor may never exceed its configured size."""
        comm = self._server_comm
        bound = comm.executor_workers if comm is not None else 0
        self.stats["grpc_executor_workers"] = bound
        ok = bound > 0 and int(self.stats["grpc_threads_max"]) <= bound
        self.stats["thread_bound_ok"] = ok
        if not ok:
            self._log(
                f"THREAD BOUND VIOLATED: saw {self.stats['grpc_threads_max']} "
                f"grpc-comm threads, executor bound {bound}"
            )

    def _write_trace(self) -> None:
        """Merge every child's injected-fault events into one fleet-wide
        FaultTrace — the record half of record/replay."""
        from fedml_tpu.scheduler.faults import FaultTrace

        clients: Dict[int, dict] = {}
        for ev in self._fault_events:
            try:
                cid, rnd, kind, detail = ev
            except (TypeError, ValueError):
                continue
            rec = clients.setdefault(int(cid), {"faults": {}})
            rec["faults"].setdefault(str(kind), []).append(
                [int(rnd), float(detail)]
            )
        for rec in clients.values():
            for rows in rec["faults"].values():
                rows.sort()
            rec["trace_complete"] = True
        trace = FaultTrace(rounds=self.spec.rounds, clients=clients)
        trace.save(os.path.join(self.out_dir, "fault_trace.json"))

    def _publish_stats(self, final: bool = False) -> None:
        from fedml_tpu.telemetry.wire import get_fleet

        snap = dict(self.stats)
        snap["recent"] = [list(e) for e in self.recent]
        snap["final"] = final
        try:
            get_fleet().set_launcher_stats(snap)
        except Exception:  # noqa: BLE001 — stats must never kill the fleet
            pass

    # -- cli mode (full `python -m fedml_tpu` ranks, same supervision) -----

    def _run_cli(self) -> None:
        spec = self.spec
        log_dir = os.path.join(self.out_dir, "logs")
        os.makedirs(log_dir, exist_ok=True)
        procs: Dict[int, dict] = {}
        t0 = time.monotonic()
        exits: Dict[int, int] = {}
        try:
            for rank in range(spec.population + 1):
                # "{rank}" in any arg expands to the process's rank, so
                # one declarative arg list can give every rank its own
                # --log_dir without 9 hand-rolled shell loops
                argv = [
                    sys.executable, "-m", "fedml_tpu", "--rank", str(rank),
                ] + [a.replace("{rank}", str(rank)) for a in spec.cli_args]
                if rank == 0:
                    argv += [
                        a.replace("{rank}", str(rank))
                        for a in spec.cli_rank0_args
                    ]
                logf = open(os.path.join(log_dir, f"rank_{rank}.log"), "w")
                procs[rank] = {
                    "proc": subprocess.Popen(
                        argv, stdout=logf, stderr=subprocess.STDOUT
                    ),
                    "log": logf,
                    "term_at": None,
                }
                self.stats["spawned"] = int(self.stats["spawned"]) + 1
            last_pub = 0.0
            while procs:
                now = time.monotonic()
                for rank in list(procs):
                    rec = procs[rank]
                    code = rec["proc"].poll()
                    if code is not None:
                        rec["log"].close()
                        exits[rank] = code
                        self.recent.append((round(now, 1), rank, code))
                        del procs[rank]
                        continue
                    if rec["term_at"] is not None:
                        if now - rec["term_at"] > _KILL_GRACE_S:
                            rec["proc"].send_signal(signal.SIGKILL)
                    elif now - t0 > spec.run_deadline_s:
                        self.stats["watchdog_expired"] = True
                        self.stats["reaped"] = (
                            int(self.stats["reaped"]) + 1
                        )
                        rec["proc"].terminate()
                        rec["term_at"] = now
                if now - last_pub >= 1.0:
                    last_pub = now
                    self.stats["live"] = len(procs)
                    self._publish_stats()
                time.sleep(0.1)
        finally:
            for rec in procs.values():
                try:
                    rec["proc"].kill()
                    rec["log"].close()
                except Exception:  # noqa: BLE001 — teardown best effort
                    pass
        bad = {r: c for r, c in exits.items() if c != 0}
        self.stats["completed"] = sum(1 for c in exits.values() if c == 0)
        self.stats["errors"] = len(bad)
        if bad:
            self.stats["bad_exits"] = {
                str(r): int(c) for r, c in sorted(bad.items())[:16]
            }
        self.stats["ok"] = not bad and not self.stats.get("watchdog_expired")
