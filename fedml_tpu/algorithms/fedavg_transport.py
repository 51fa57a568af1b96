"""Distributed FedAvg over the Message/Observer transport — true cross-silo
federation (ref: fedml_api/distributed/fedavg/{FedAvgServerManager.py,
FedAvgClientManager.py, FedAVGAggregator.py, FedAVGTrainer.py,
message_define.py}).

This is the reference's flagship 6-file pattern collapsed into one module.
The server runs the round FSM (all-received barrier → weighted aggregate →
resample → broadcast, ref FedAvgServerManager.py:34-72); clients run the
jit-compiled local-train scan and upload weights. Unlike the intra-pod
shard_map path (fedml_tpu.parallel), participants here are independent
processes/hosts talking through any BaseCommManager (loopback in tests,
gRPC across machines). Weights travel as binary buffers (core/message.py),
not JSON lists."""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algorithms.fedavg import weighted_average
from fedml_tpu.config import RunConfig
from fedml_tpu.telemetry import ClientHealthRegistry, get_comm_meter, get_tracer
from fedml_tpu.core.comm import BaseCommManager
from fedml_tpu.core.loopback import LoopbackCommManager, LoopbackHub
from fedml_tpu.core.managers import ClientManager, ServerManager
from fedml_tpu.core.message import Message, MessageType as MT
from fedml_tpu.data.base import FederatedDataset, stack_clients
from fedml_tpu.models import ModelDef
from fedml_tpu.train.client import make_local_train
from fedml_tpu.train.evaluate import evaluate


class FedAvgAggregator:
    """Server-side accumulate + weighted average (ref FedAVGAggregator.py:
    37-78: add_local_trained_result, check_whether_all_receive, aggregate)."""

    def __init__(self, worker_num: int):
        self.worker_num = worker_num
        self.model_dict: Dict[int, dict] = {}
        self.sample_num_dict: Dict[int, float] = {}
        self._flags = [False] * worker_num

    def add_local_trained_result(self, index: int, params: dict, num_samples: float) -> None:
        self.model_dict[index] = params
        self.sample_num_dict[index] = float(num_samples)
        self._flags[index] = True

    def check_whether_all_receive(self) -> bool:
        return all(self._flags)

    def received_count(self) -> int:
        return len(self.model_dict)

    def aggregate(self) -> dict:
        idxs = sorted(self.model_dict)
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack([jnp.asarray(l) for l in leaves]),
            *[self.model_dict[i] for i in idxs],
        )
        weights = jnp.asarray(
            [self.sample_num_dict[i] for i in idxs], jnp.float32
        )
        avg = weighted_average(stacked, weights)
        self.model_dict.clear()
        self.sample_num_dict.clear()
        self._flags = [False] * self.worker_num
        return jax.device_get(avg)


def _model_wire_cost(tree) -> tuple:
    """(as-shipped, fp32-equivalent) bytes of one model broadcast — the
    downlink mirror of the uplink's arithmetic accounting (no cast copy
    materialized; 4 B x element count for the raw denominator)."""
    leaves = jax.tree_util.tree_leaves(tree)
    shipped = sum(int(np.asarray(a).nbytes) for a in leaves)
    raw = 4 * sum(int(np.size(a)) for a in leaves)
    return shipped, raw


def local_train_key_fields(model: ModelDef, config: RunConfig, task: str):
    """THE digest key of the shared transport local-train program — one
    definition serving both the factory below and the admission
    controller's warm-program probe (fedml_tpu/serve/admission.py
    recomputes a candidate tenant's digest to price its compile cost
    from the content-addressed store; a drifted copy of these fields
    would silently price the wrong program)."""
    from fedml_tpu.compile import model_fingerprint

    return {
        "kind": "local_train",
        "model": model_fingerprint(model),
        "train": config.train,
        "epochs": config.fed.epochs,
        "task": task,
    }


def shared_local_train(model: ModelDef, config: RunConfig, task: str):
    """THE jitted client local-train program for a transport federation,
    deduped through the process-wide ProgramCache (fedml_tpu/compile/):
    every LocalTrainer, every runner, and every test module building the
    same (model, train config, epochs, task) shares one compile."""
    from fedml_tpu.compile import get_program_cache

    return get_program_cache().get_or_build(  # fedlint: disable=baked-constant -- key fields are the dict literal in local_train_key_fields directly above, shared verbatim with the admission controller's pricing probe (serve/admission.py) so the two can never drift; the helper reads only digested leaves (model fingerprint, config.train, epochs, task)
        "local_train",
        local_train_key_fields(model, config, task),
        lambda: jax.jit(
            make_local_train(model, config.train, config.fed.epochs, task=task)
        ),
    )


class LocalTrainer:
    """Client-side trainer wrapper (ref FedAVGTrainer.py:7-54: update_dataset
    by client_index, train(round) -> (weights, local_sample_number))."""

    def __init__(
        self,
        config: RunConfig,
        data: FederatedDataset,
        model: ModelDef,
        task: str,
        local_train_fn=None,
        straggle_s: float = 0.0,
    ):
        self.config = config
        self.data = data
        self.model = model
        # Share one jitted fn across in-process trainers — K distinct
        # closures would defeat the jit cache and compile K times. The
        # program cache (fedml_tpu/compile/) extends that sharing across
        # trainer instances and processes' test modules.
        self.local_train = local_train_fn or shared_local_train(
            model, config, task
        )
        self.client_index = 0
        # Simulated compute heterogeneity: sleep this long after every
        # local training (a slow phone among fast ones). Drives the
        # straggler/async benchmarks; 0 = off.
        self.straggle_s = float(straggle_s)
        # last local mean train loss — attached to the upload message so
        # the server can feed power_of_choice selection (scheduler/)
        self.last_loss: Optional[float] = None

    def update_dataset(self, client_index: int):
        self.client_index = int(client_index)

    def train(self, round_idx: int, variables: dict):
        with get_tracer().span(
            "local_train", client=int(self.client_index), round=int(round_idx)
        ):
            return self._train(round_idx, variables)

    def _train(self, round_idx: int, variables: dict):
        cfg = self.config
        batch = stack_clients(
            self.data,
            [self.client_index],
            cfg.data.batch_size,
            # client_index folded in: otherwise every client in a round
            # would draw the identical shuffle permutation.
            seed=cfg.seed * 1_000_003 + round_idx * 8191 + self.client_index,
            pad_bucket=cfg.data.pad_bucket,
        )
        rng = jax.random.fold_in(
            jax.random.PRNGKey(cfg.seed), (round_idx + 1) * 7919 + self.client_index
        )
        new_vars, m = self.local_train(
            variables,
            jnp.asarray(batch.x[0]),
            jnp.asarray(batch.y[0]),
            jnp.asarray(batch.mask[0]),
            rng,
        )
        n = len(self.data.client_y[self.client_index])
        out = jax.device_get(new_vars)
        try:
            count = float(np.asarray(m["count"]))
            # a zero-sample shard has no loss signal: report None (upload
            # omits ARG_TRAIN_LOSS, client stays "cold" for
            # power_of_choice) exactly like the sim's c > 0 skip in
            # _report_client_losses — a fabricated 0.0 would rank the
            # client last in sim/transport-divergent ways
            self.last_loss = (
                float(np.asarray(m["loss_sum"])) / count if count > 0 else None
            )
        except (KeyError, TypeError):  # custom local_train_fn metric shape
            self.last_loss = None
        if self.straggle_s:
            time.sleep(self.straggle_s)
        return out, n


class FedAvgServerManager(ServerManager):
    """Round FSM (ref FedAvgServerManager.py:20-72)."""

    def __init__(
        self,
        config: RunConfig,
        comm: BaseCommManager,
        model: ModelDef,
        data: Optional[FederatedDataset] = None,
        task: str = "classification",
        worker_num: Optional[int] = None,
        log_fn=None,
        server_opt: bool = False,
        faults=None,
    ):
        super().__init__(comm, rank=0, config=config)
        self.config = config
        self.model = model
        self.data = data
        self.task = task
        self.log_fn = log_fn or (lambda m: None)
        self.worker_num = worker_num or config.fed.client_num_per_round
        self.aggregator = FedAvgAggregator(self.worker_num)
        # secure-agg mode: masked field vectors keyed by party (rank-1).
        # Clients size the mask registry from client_num_per_round (the
        # only value they have), so a worker_num override would give the
        # two wire ends non-cancelling masks — reject it up front.
        if config.comm.secure_agg and self.worker_num != config.fed.client_num_per_round:
            raise ValueError(
                f"secure_agg requires worker_num ({self.worker_num}) == "
                f"client_num_per_round ({config.fed.client_num_per_round}): "
                "clients derive the mask registry from the latter"
            )
        # downlink quantization (CommConfig.downlink_compression): int8
        # only — the top-k family zeroes model coordinates outright, which
        # is a delta codec's trick, not a model broadcast's
        dl = config.comm.downlink_compression
        if dl not in ("none", "int8"):
            raise ValueError(
                f"downlink_compression supports 'none' or 'int8'; got {dl!r}"
            )
        if config.comm.secure_agg and dl != "none":
            # masked uploads are field vectors over the EXACT broadcast
            # reference; requantizing the reference each round would put
            # the two wire ends in different fields
            raise ValueError(
                "secure_agg and downlink_compression are mutually exclusive"
            )
        self._masked_uploads: Dict[int, np.ndarray] = {}
        self._masked_ns: Dict[int, float] = {}
        # client-held-key exchange state (secagg/secure_aggregation.py
        # ClientParty/ServerAggregator): the server holds PUBLIC keys only
        self._round_pks: Dict[int, int] = {}  # party -> pk, this round
        self._recovery_pending = False
        self._recovery_vecs: Dict[int, np.ndarray] = {}  # survivor party -> vec
        self._recovery_requested_for = None  # dropped-set of the last request
        self._registry_sent = False
        # FedOpt over the transport (the reference's fedopt IS a
        # distributed MPI algorithm, FedOptAggregator.py:95-117): apply the
        # server optimizer to the pseudo-gradient after each aggregate.
        self._server_step = None
        self._server_opt_state = None
        if server_opt:
            # one registration point (fedopt.make_cached_server_step) so
            # this manager and the vmap/mesh APIs can never drift apart in
            # how they key the shared server-step program
            from fedml_tpu.algorithms.fedopt import make_cached_server_step

            self._server_step, self._server_optimizer = (
                make_cached_server_step(config)
            )
        self.round_idx = 0
        # Straggler deadline state (FedConfig.deadline_s/min_clients). The
        # timer thread races the comm receive loop; _round_lock serializes
        # round completion.
        self._round_lock = threading.Lock()
        self._deadline_timer: Optional[threading.Timer] = None
        self._deadline_passed = False
        # Stalled-round abandonment: a round can sit below quorum FOREVER
        # when every sampled client crashed/dropped — reachable on purpose
        # under a participation-fault plan, and ONLY then (without one,
        # every sampled client eventually uploads, and the legacy
        # semantics — close on the quorum-th upload whenever it arrives,
        # however late past the deadline — must stay untouched: a cold
        # first-round jit compile can outlast several deadline_s). With
        # the valve armed, each below-quorum deadline re-arms the timer;
        # after 3 consecutive firings with NO new upload the round is
        # abandoned with whatever arrived (possibly nothing — the model
        # then carries over unchanged), loudly, instead of hanging.
        #
        # The plan is read off the ONE FaultInjector the runner plumbs in
        # (run_federation) — re-parsing FedConfig.fault_plan here would
        # re-read the plan file and open a drift window where the valve
        # and the injected faults disagree (a plan file swapped between
        # the two parses). Direct constructions without an injector
        # (grpc rank 0: the clients inject in their own processes) parse
        # once as a fallback.
        self.faults = faults
        if faults is not None:
            _plan = faults.plan
        else:
            from fedml_tpu.scheduler import FaultPlan

            _plan = FaultPlan.from_config(config)
        self._stall_valve = (
            _plan is not None and _plan.has_participation_faults()
        )
        self._stall_last_count = -1
        self._stall_strikes = 0
        # graceful per-tenant drain (fedml_tpu/serve/): when set, the
        # round that is currently open completes normally and the
        # federation then FINISHes instead of broadcasting the next round;
        # _federation_done marks the FINISH having happened, so a late
        # request_stop cannot fabricate an extra zero-upload round
        self._stop_requested = False
        self._federation_done = False
        self.abandoned_rounds = 0
        self.dropped_uploads = 0  # late round-tagged uploads discarded
        self._dead_workers: set = set()  # peers whose broadcasts failed
        self.deadline_error: Optional[BaseException] = None
        self.global_vars = jax.device_get(
            model.init(jax.random.fold_in(jax.random.PRNGKey(config.seed), 0))
        )
        self.history: List[dict] = []
        from fedml_tpu.train.evaluate import make_eval_fn

        self._eval_fn = make_eval_fn(model, task) if data is not None else None
        # Telemetry: the client health registry feeds on the span stream
        # (in-process federations record true local_train wall time) and on
        # this server's broadcast→upload round-trips (the only timing a
        # cross-process gRPC server can see); (client, round) dedupe keeps
        # the two sources from double counting. Round-lifecycle spans begin
        # at broadcast and end at round completion (possibly on another
        # thread), so they use explicit handles, not context managers.
        self._tracer = get_tracer()
        self.health = ClientHealthRegistry.from_config(config).attach(self._tracer)
        self._round_span = None
        self._assigned: Dict[int, tuple] = {}  # worker -> (client_idx, t_bcast)
        # Scheduler: the SAME policy driver the vmap simulator uses
        # (scheduler/policies.py), so both runtimes select byte-identical
        # cohorts from one config — a test contract. The server passes its
        # worker_num as the final k (run_federation already provisions one
        # worker per overprovisioned slot); straggler_aware feeds on this
        # health registry, power_of_choice on the uploads' train losses.
        from fedml_tpu.scheduler import ClientScheduler

        self.scheduler = ClientScheduler.from_config(
            config,
            num_clients=config.fed.client_num_in_total,
            data=data,
            log_fn=self.log_fn,
            health=self.health,
            tracer=self._tracer,
        )
        # Wire telemetry (telemetry/wire.py): client beacons piggybacked
        # on uploads feed the CLI's flight recorder (when one listens on
        # this tracer) and the process fleet aggregator. Dedupe is the
        # worker's last consumed round — a flaky duplicate delivery
        # restates the SAME beacon and must not double-count.
        from fedml_tpu.telemetry.flight import attached_recorder

        self._flight = attached_recorder(self._tracer)
        self._beacon_seen: Dict[int, int] = {}

    def finish(self):
        # stop feeding the health registry from the global span stream —
        # sequential federations in one process (tests, sweeps) must not
        # accumulate listeners; queries on self.health keep working
        self.health.detach()
        super().finish()

    def request_stop(self, drain: bool = True) -> None:
        """Graceful per-tenant stop (fedml_tpu/serve/): ``drain=True``
        lets the currently-open round complete (its cohort's work is not
        thrown away) and FINISHes the fleet instead of broadcasting the
        next round; ``drain=False`` additionally closes the open round
        immediately with whatever uploads have arrived (the zero-upload
        carry-over path applies — the model survives unchanged). Safe
        from any thread EXCEPT this server's own message handlers (it
        takes the round lock); handlers set ``_stop_requested`` directly
        instead."""
        self._stop_requested = True
        if drain:
            return
        with self._round_lock:
            # a federation that already FINISHed (naturally or via an
            # earlier stop) has no open round: completing again would log
            # a spurious zero-upload row and re-broadcast FINISH
            if not self._federation_done:
                self._complete_round()

    def _broadcast(self, msg: Message) -> bool:
        """Send a server->client message, tolerating a dead peer: a client
        process that crashed mid-federation must not take the server FSM
        down with it — the deadline/quorum machinery (FedConfig.deadline_s/
        min_clients) absorbs the missing upload instead (chaos
        tolerance; the reference's aggregator barrier would hang
        forever, FedAVGAggregator.py:43-49).

        A worker whose send failed is remembered as dead and skipped (each
        skipped round logs once) — without this, every round would re-pay
        the transport's failure timeout inside the round lock. Any message
        later RECEIVED from that worker clears the flag (elastic re-entry,
        commit c8cb247's documented stance)."""
        worker = msg.get_receiver_id()
        if worker in self._dead_workers:
            logging.info("skipping broadcast to dead worker %d", worker)
            return False
        try:
            self.send_message(msg)
            return True
        except Exception as e:  # noqa: BLE001 — transport errors vary by backend
            self._dead_workers.add(worker)
            logging.warning(
                "broadcast to worker %d failed (%s) — continuing on quorum",
                worker,
                e,
            )
            return False

    def send_init_msg(self):
        """Sample the opening round's clients, broadcast the model (ref
        send_init_msg :20-28). The opening round is ``self.round_idx`` —
        0 unless a session resume poured a checkpoint in first
        (fedml_tpu/serve/session.py), in which case the scheduler's
        restored memo re-selects the in-flight cohort byte-identically."""
        self._t0 = time.monotonic()
        # _complete_round (the steady-state sender) runs entirely under
        # _round_lock; the opening round must too, or its writes to
        # _round_span / global_vars / the deadline scaffolding race the
        # first client uploads arriving on the comm thread
        with self._round_lock:
            r = self.round_idx
            sampled = self.scheduler.select(r, k=self.worker_num)
            self._round_span = self._tracer.start_span("round", round=r)
            with self._tracer.span("broadcast", round=r):
                self._broadcast_round(MT.S2C_INIT_CONFIG, r, sampled)
            self._arm_deadline()

    def _broadcast_round(self, msg_type: str, round_idx: int, sampled):
        """Ship the round's model to the sampled cohort, encoding the
        payload ONCE per round instead of once per worker.

        The model tree is host-materialised contiguous up front, so every
        worker's Message references the SAME buffers and the envelope's
        per-param ``ascontiguousarray`` is a no-op — K workers cost one
        model copy, not K (the wire cost is computed once too). With
        ``CommConfig.downlink_compression`` the tree is int8-quantized
        once and the DEQUANTIZED tree becomes the round's reference model
        (``self.global_vars``): clients train from exactly it, compressed
        uplink deltas decode against exactly it, and the next pseudo-
        gradient is measured from exactly it — both wire ends agree
        byte-for-byte on the round's starting point."""
        host = jax.tree_util.tree_map(
            lambda a: np.ascontiguousarray(np.asarray(a)), self.global_vars
        )
        dl = self.config.comm.downlink_compression
        payload = None
        if dl != "none":
            from fedml_tpu.core import compression as CZ

            payload = CZ.encode_delta(host, dl, self.config.comm.topk_frac)
            self.global_vars = CZ.decode_delta(payload, host, dl)
            shipped = CZ.payload_bytes(payload)
            raw = 4 * sum(
                int(np.size(a)) for a in jax.tree_util.tree_leaves(host)
            )
        else:
            self.global_vars = host
            shipped, raw = _model_wire_cost(host)
        for worker, client_idx in enumerate(sampled, start=1):
            msg = Message(msg_type, 0, worker)
            if payload is not None:
                msg.add_params(MT.ARG_MODEL_QUANT, payload)
                msg.add_params(MT.ARG_MODEL_CODEC, dl)
            else:
                msg.add_params(MT.ARG_MODEL_PARAMS, host)
            msg.add_params(MT.ARG_CLIENT_INDEX, int(client_idx))
            msg.add_params(MT.ARG_ROUND_IDX, round_idx)
            self._assigned[worker] = (int(client_idx), time.monotonic())
            if self._broadcast(msg):
                get_comm_meter().on_downlink(shipped, raw)

    def register_message_receive_handlers(self):
        self.register_message_receive_handler(
            MT.C2S_SEND_MODEL, self._on_model_from_client
        )
        self.register_message_receive_handler(MT.C2S_PUBKEY, self._on_pubkey)
        self.register_message_receive_handler(
            MT.C2S_RECOVERY, self._on_recovery
        )

    # -- secure-agg key exchange (round structure of Bonawitz et al.:
    #    advertise keys -> masked input -> unmask; the server relays public
    #    keys and never holds a party secret) --
    def _send_registry(self):
        """Broadcast the pk registry of the parties heard so far. Caller
        holds _round_lock. Parties that never advertised a key are simply
        not in the round's mask algebra (Bonawitz proceeds with surviving
        parties), so a client dead before its pubkey cannot deadlock the
        key phase."""
        self._registry_sent = True
        parties = sorted(self._round_pks)
        for p in parties:
            out = Message(MT.S2C_PUBKEYS, 0, p + 1)
            out.add_params(MT.ARG_ROUND_IDX, self.round_idx)
            out.add_params(
                MT.ARG_PUBKEY_REGISTRY,
                {
                    "parties": parties,
                    "pks": [self._round_pks[q] for q in parties],
                },
            )
            self._broadcast(out)

    def _on_pubkey(self, msg: Message):
        self._dead_workers.discard(msg.get_sender_id())
        with self._round_lock:
            if msg.get(MT.ARG_ROUND_IDX, -1) != self.round_idx:
                return
            if self._registry_sent:
                # the round's registry is sealed: a late advertiser was
                # never part of the mask algebra, and recording it would
                # later misclassify it as a dropped party (whose "masks"
                # no survivor ever applied or could recover)
                self.dropped_uploads += 1
                return
            party = msg.get_sender_id() - 1
            self._round_pks[party] = int(msg.get(MT.ARG_PUBKEY))
            if len(self._round_pks) == self.worker_num or (
                self._deadline_passed
                and len(self._round_pks) >= self._quorum()
            ):
                self._send_registry()

    def _on_recovery(self, msg: Message):
        self._dead_workers.discard(msg.get_sender_id())
        with self._round_lock:
            if msg.get(MT.ARG_ROUND_IDX, -1) != self.round_idx:
                return
            answered = set(map(int, msg.get(MT.ARG_DROPPED) or ()))
            if self._recovery_requested_for is None or answered != set(
                self._recovery_requested_for
            ):
                # stale response for an earlier, smaller dropped set —
                # accepting it would bake uncancelled pair masks of the
                # newly-dropped survivors into the aggregate
                return
            party = msg.get_sender_id() - 1
            self._recovery_vecs[party] = np.asarray(
                msg.get(MT.ARG_RECOVERY_VEC), np.int64
            )
            if self._recovery_pending and set(self._recovery_vecs) >= set(
                self._masked_uploads
            ):
                self._complete_round()

    def _on_recovery_deadline(self, armed_round: int):
        """A survivor that never answered its S2C_RECOVER (it died after
        uploading) becomes a dropped party itself: discard its upload and
        restart the recovery exchange with the remaining survivors. The
        survivor set strictly shrinks each iteration, so this terminates."""
        try:
            with self._round_lock:
                if armed_round != self.round_idx or not self._recovery_pending:
                    return
                silent = set(self._masked_uploads) - set(self._recovery_vecs)
                for p in silent:
                    self._masked_uploads.pop(p, None)
                    self._masked_ns.pop(p, None)
                self._recovery_requested_for = None  # force a re-request
                self._complete_round()
        except BaseException as e:  # noqa: BLE001 — see _on_deadline
            self.deadline_error = e
            self.finish()

    # -- straggler deadline (FedConfig.deadline_s) --
    def _arm_deadline(self):
        dl = self.config.fed.deadline_s
        if not dl:
            return
        self._deadline_passed = False
        self._stall_last_count = -1
        self._stall_strikes = 0
        # round generation captured at arm time: cancel() cannot stop a
        # callback already blocked on _round_lock, so a stale timer must
        # recognise that its round has already completed
        self._deadline_timer = threading.Timer(
            dl, self._on_deadline, args=(self.round_idx,)
        )
        self._deadline_timer.daemon = True
        self._deadline_timer.start()

    def _disarm_deadline(self):
        if self._deadline_timer is not None:
            self._deadline_timer.cancel()
            self._deadline_timer = None
        self._deadline_passed = False

    def _quorum(self) -> int:
        return max(1, min(self.config.fed.min_clients, self.worker_num))

    def _received_count(self) -> int:
        if self.config.comm.secure_agg:
            return len(self._masked_uploads)
        return self.aggregator.received_count()

    def _on_deadline(self, armed_round: int):
        try:
            with self._round_lock:
                if armed_round != self.round_idx:
                    return  # stale timer: its round already completed
                self._deadline_passed = True
                if (
                    self.config.comm.secure_agg
                    and not self._registry_sent
                    and len(self._round_pks) >= self._quorum()
                ):
                    # key phase stalled on a client that died before its
                    # pubkey: proceed with the parties heard so far (their
                    # uploads can still reach quorum before this same
                    # deadline flag completes the round)
                    self._send_registry()
                    return
                if self._received_count() >= self._quorum():
                    self._complete_round()
                    return
                if not self._stall_valve:
                    # legacy semantics (no participation faults): the
                    # quorum-th upload completes the round on arrival —
                    # _on_model_from_client checks _deadline_passed
                    return
                # Below quorum under a droppy fault plan: keep the flag
                # set (the quorum-th upload still completes the round on
                # arrival) and re-arm so stall detection keeps ticking.
                # Three consecutive deadlines with NO new upload = a round
                # that can never close (the whole cohort crashed/dropped):
                # abandon it with whatever arrived rather than hang —
                # quorum is a liveness floor, not worth a wedged
                # federation (logged loudly).
                n = self._received_count()
                if n == self._stall_last_count:
                    self._stall_strikes += 1
                else:
                    self._stall_last_count = n
                    self._stall_strikes = 0
                if self._stall_strikes >= 2:  # 3rd barren deadline
                    logging.warning(
                        "round %d stalled below quorum (%d/%d uploads "
                        "after 3 deadlines) — abandoning with the "
                        "partial set",
                        self.round_idx, n, self._quorum(),
                    )
                    self.abandoned_rounds += 1
                    self._complete_round()
                    return
                t = threading.Timer(
                    self.config.fed.deadline_s,
                    self._on_deadline,
                    args=(armed_round,),
                )
                t.daemon = True
                t.start()
                self._deadline_timer = t
        except BaseException as e:  # noqa: BLE001
            # the timer thread would otherwise swallow this and leave the
            # server parked on its inbox forever; surface it through finish()
            self.deadline_error = e
            self.finish()

    def _on_model_from_client(self, msg: Message):
        self._dead_workers.discard(msg.get_sender_id())
        with self._round_lock:
            # missing tag (pre-tag client version) fails SAFE: -1 never
            # matches, so an unattributable upload is dropped, not averaged
            # into whatever round happens to be open
            upload_round = msg.get(MT.ARG_ROUND_IDX, -1)
            if upload_round == -1:
                logging.warning(
                    "dropping untagged model upload from sender %s "
                    "(client protocol predates round tags?)",
                    msg.get_sender_id(),
                )
            if upload_round != self.round_idx:
                # straggler reporting for an already-closed round
                self.dropped_uploads += 1
                return
            # health: broadcast→upload round-trip for this worker's client
            # (no-op when the span stream already recorded the round)
            assigned = self._assigned.get(msg.get_sender_id())
            if assigned is not None:
                rtt_s = time.monotonic() - assigned[1]
                # telemetry beacon first: its MEASURED train time is truer
                # than the rtt fallback below, which the (client, round)
                # dedupe then absorbs
                beacon = msg.get(MT.ARG_TELEMETRY)
                if beacon is not None:
                    self._consume_beacon(
                        msg.get_sender_id(), assigned[0], upload_round,
                        beacon, rtt_s,
                    )
                self.health.observe_train(assigned[0], upload_round, rtt_s)
                # power_of_choice bias signal: the client's local mean
                # train loss rides the upload (ARG_TRAIN_LOSS)
                loss = msg.get(MT.ARG_TRAIN_LOSS)
                if loss is not None:
                    self.scheduler.report_loss(assigned[0], float(loss))
            worker = msg.get_sender_id() - 1
            if self.config.comm.secure_agg:
                # store the masked vector; unmasking happens once at round
                # completion (dropout masks recovered there if a quorum
                # round closed without some parties)
                masked = msg.get(MT.ARG_MASKED_UPDATE)
                if masked is None:
                    raise ValueError(
                        f"secure-agg server received an unmasked upload "
                        f"from sender {msg.get_sender_id()} — was that "
                        "client launched without --secure_agg?"
                    )
                if self._recovery_pending:
                    # a "dropped" party's upload racing the recovery
                    # exchange: its masks are being unwound — including it
                    # now would corrupt the sum
                    self.dropped_uploads += 1
                    return
                self._masked_uploads[worker] = masked
                self._masked_ns[worker] = float(msg.get(MT.ARG_NUM_SAMPLES))
                if len(self._masked_uploads) == self.worker_num or (
                    self._deadline_passed
                    and len(self._masked_uploads) >= self._quorum()
                ):
                    self._complete_round()
                return
            params = msg.get(MT.ARG_MODEL_PARAMS)
            if params is None:
                # compressed uplink: reconstruct against this round's
                # broadcast model (the round tag above guarantees the
                # upload belongs to the currently open round). The codec
                # comes from the MESSAGE's protocol tag, so a client whose
                # --compression differs from the server's still decodes
                # correctly instead of wedging the FSM.
                payload = msg.get(MT.ARG_MODEL_DELTA)
                method = msg.get(MT.ARG_COMPRESSION)
                if payload is None or method is None:
                    raise ValueError(
                        f"model upload from sender {msg.get_sender_id()} "
                        "carries neither model_params nor a tagged "
                        "compressed delta"
                    )
                from fedml_tpu.core import compression as CZ

                params = CZ.decode_update(payload, self.global_vars, method)
            self.aggregator.add_local_trained_result(
                worker, params, msg.get(MT.ARG_NUM_SAMPLES)
            )
            if self.aggregator.check_whether_all_receive() or (
                self._deadline_passed
                and self.aggregator.received_count() >= self._quorum()
            ):
                self._complete_round()

    def _consume_beacon(
        self, worker: int, client_idx: int, round_idx: int,
        beacon, rtt_s: float,
    ) -> None:
        """Fold one client telemetry beacon (telemetry/wire.py) into
        health, flight, and fleet. Consumed at most once per (worker,
        round): a flaky/retried upload restates the SAME beacon, and the
        bytes were metered client-side at attach, so duplicates are
        attribution no-ops here. Caller holds _round_lock."""
        if not isinstance(beacon, dict):
            return
        if self._beacon_seen.get(worker) == round_idx:
            return
        self._beacon_seen[worker] = int(round_idx)
        try:
            train_s = max(0.0, float(beacon.get("train_s", 0.0)))
            encode_s = max(0.0, float(beacon.get("encode_s", 0.0)))
        except (TypeError, ValueError):
            return
        tier = beacon.get("tier")
        self.health.observe_train(client_idx, round_idx, train_s, tier=tier)
        from fedml_tpu.telemetry import get_fleet

        get_fleet().observe_beacon(tier, beacon, rtt_s=rtt_s)
        if self._flight is not None:
            # the measured train-vs-wire-vs-queue split: whatever the
            # round trip spent beyond training+encoding sat on the wire
            # or in a queue
            self._flight.observe_beacon(
                round_idx, train_s, encode_s,
                wire_s=max(0.0, rtt_s - train_s - encode_s),
            )

    def _complete_round(self):
        """Aggregate whatever has arrived, eval, resample, broadcast.
        Caller holds _round_lock."""
        self._disarm_deadline()
        zero_uploads = False
        if self.config.comm.secure_agg:
            from fedml_tpu.secagg.secure_aggregation import (
                ServerAggregator,
                tree_dim,
            )

            dropped = sorted(set(self._round_pks) - set(self._masked_uploads))
            if dropped and self._recovery_requested_for != set(dropped):
                # Bonawitz unmask round: registry parties that never
                # uploaded left uncancelled pair masks inside the
                # survivors' uploads — ask each survivor for its recovery
                # contribution; the round completes in _on_recovery. A
                # survivor whose request cannot even be SENT is dead too:
                # drop its upload and re-enter with the larger dropped set
                # (strictly shrinking survivors ⇒ terminates). A recovery
                # timer catches survivors that died without closing their
                # socket (_on_recovery_deadline).
                self._recovery_pending = True
                self._recovery_requested_for = set(dropped)
                self._recovery_vecs = {}
                unreachable = []
                for p in sorted(self._masked_uploads):
                    out = Message(MT.S2C_RECOVER, 0, p + 1)
                    out.add_params(MT.ARG_ROUND_IDX, self.round_idx)
                    out.add_params(MT.ARG_DROPPED, list(map(int, dropped)))
                    if not self._broadcast(out):
                        unreachable.append(p)
                if unreachable:
                    for p in unreachable:
                        self._masked_uploads.pop(p, None)
                        self._masked_ns.pop(p, None)
                    self._recovery_requested_for = None
                    self._complete_round()
                    return
                if self._masked_uploads:
                    t = threading.Timer(
                        max(self.config.fed.deadline_s, 5.0),
                        self._on_recovery_deadline,
                        args=(self.round_idx,),
                    )
                    t.daemon = True
                    t.start()
                    return
            if dropped and set(self._recovery_vecs) < set(self._masked_uploads):
                return  # waiting on recovery vecs (timer bounds the wait)
            srv = ServerAggregator(tree_dim(self.global_vars))
            if self._masked_uploads:
                with self._tracer.span(
                    "aggregate",
                    round=self.round_idx,
                    n_uploads=len(self._masked_uploads),
                    secure_agg=True,
                ):
                    total = srv.masked_sum(self._masked_uploads)
                    if dropped:
                        total = srv.remove_dropout_masks(
                            total, self._recovery_vecs
                        )
                    ns = {p: self._masked_ns[p] for p in self._masked_uploads}
                    avg = srv.decode_average(total, ns, self.global_vars)
            else:
                # every party died mid-protocol: keep the current model
                logging.warning(
                    "secure-agg round %d lost every upload — model unchanged",
                    self.round_idx,
                )
                avg = self.global_vars
                zero_uploads = True
            self._masked_uploads, self._masked_ns = {}, {}
            self._round_pks, self._recovery_vecs = {}, {}
            self._recovery_pending = False
            self._recovery_requested_for = None
            self._registry_sent = False
        elif self.aggregator.received_count() == 0:
            # abandoned round with zero uploads (entire cohort
            # crashed/dropped): the model carries over unchanged
            logging.warning(
                "round %d closed with no uploads — model unchanged",
                self.round_idx,
            )
            avg = self.global_vars
            zero_uploads = True
        else:
            with self._tracer.span(
                "aggregate",
                round=self.round_idx,
                n_uploads=self.aggregator.received_count(),
            ):
                avg = self.aggregator.aggregate()
        if self._server_step is not None and not zero_uploads:
            # a zero-upload round must not step the server optimizer: the
            # pseudo-gradient is exactly zero, but momentum/Adam moments
            # from earlier rounds would still move the model and decay the
            # state on a round in which no client trained
            if self._server_opt_state is None:
                self._server_opt_state = self._server_optimizer.init(
                    self.global_vars["params"]
                )
            self.global_vars, self._server_opt_state = jax.device_get(
                self._server_step(self.global_vars, avg, self._server_opt_state)
            )
        else:
            self.global_vars = avg
        row = {
            "round": self.round_idx,
            # wall clock since w0 went out — the async bench's
            # accuracy-at-matched-wall-clock comparison keys on this
            "t_s": round(time.monotonic() - getattr(self, "_t0", time.monotonic()), 3),
        }
        eval_now = self.data is not None and (
            self.round_idx % self.config.fed.frequency_of_the_test == 0
            or self.round_idx == self.config.fed.comm_round - 1
        )
        if eval_now:
            with self._tracer.span("eval", round=self.round_idx):
                loss, acc = evaluate(
                    self.model,
                    self.global_vars,
                    self.data.test_x,
                    self.data.test_y,
                    task=self.task,
                    eval_fn=self._eval_fn,
                )
            row["Test/Loss"], row["Test/Acc"] = loss, acc
        self.history.append(row)
        self.log_fn(row)
        if self._round_span is not None:
            self._round_span.end()
            self._round_span = None
        self.round_idx += 1
        if self.round_idx >= self.config.fed.comm_round or self._stop_requested:
            self._federation_done = True
            for worker in range(1, self.worker_num + 1):
                self._broadcast(Message(MT.FINISH, 0, worker))
            self.finish()
            return
        sampled = self.scheduler.select(self.round_idx, k=self.worker_num)
        self._round_span = self._tracer.start_span("round", round=self.round_idx)
        with self._tracer.span("broadcast", round=self.round_idx):
            self._broadcast_round(MT.S2C_SYNC_MODEL, self.round_idx, sampled)
        self._arm_deadline()


class FedAvgClientManager(ClientManager):
    """ref FedAvgClientManager.py:17-65."""

    def __init__(
        self,
        config: RunConfig,
        comm: BaseCommManager,
        rank: int,
        trainer: LocalTrainer,
        ef=None,
        faults=None,
    ):
        super().__init__(comm, rank, config=config)
        self.config = config
        self.trainer = trainer
        # fault injection (scheduler/faults.FaultInjector, usually shared
        # across a federation's client actors): consulted per assignment —
        # dropout skips training+upload, crash makes the CLIENT silent for
        # every round from crash_at_round on (faults follow the client,
        # not this worker slot — the sampler re-assigns clients to workers
        # each round), slowdown sleeps, flaky double-sends the upload
        self._faults = faults
        # TopKErrorFeedback store. The residual must follow the CLIENT, and
        # sampling re-assigns clients to ranks every round — so in-process
        # runtimes SHARE one store across all client actors (run_federation
        # passes it in); a per-process store (grpc) is only sound under
        # rank-stable assignment, which the CLI enforces (full
        # participation).
        if ef is None:
            from fedml_tpu.core.compression import ErrorFeedback

            ef = ErrorFeedback.maybe_from_config(config.comm)
        self._ef = ef
        # secure-agg per-round state: the ClientParty holding THIS client's
        # secret key (never serialized, never sent)
        self._secagg_party = None
        self._secagg_round = -1
        self._secagg_pending = None
        # quantized-downlink decode template (shapes/treedef only; leaf
        # VALUES are never read) — built lazily on the first quantized sync
        self._downlink_template = None

    def register_message_receive_handlers(self):
        self.register_message_receive_handler(MT.S2C_INIT_CONFIG, self._on_sync)
        self.register_message_receive_handler(MT.S2C_SYNC_MODEL, self._on_sync)
        self.register_message_receive_handler(MT.S2C_PUBKEYS, self._on_pubkeys)
        self.register_message_receive_handler(MT.S2C_RECOVER, self._on_recover)
        self.register_message_receive_handler(MT.FINISH, lambda m: self.finish())

    # -- secure-agg client phases (client-held keys): train + advertise a
    #    FRESH locally-generated DH public key, upload the masked update
    #    once the server relays the round's registry, answer a recovery
    #    request if some registry party dropped before uploading --
    def _on_pubkeys(self, msg: Message):
        if self._secagg_party is None or msg.get(MT.ARG_ROUND_IDX) != self._secagg_round:
            return
        reg = msg.get(MT.ARG_PUBKEY_REGISTRY)
        pks = {
            int(p): int(pk) for p, pk in zip(reg["parties"], reg["pks"])
        }
        self._secagg_party.set_registry(pks)
        weights, w_round, n = self._secagg_pending
        out = Message(MT.C2S_SEND_MODEL, self.rank, 0)
        out.add_params(
            MT.ARG_MASKED_UPDATE,
            self._secagg_party.masked_update(weights, w_round, n),
        )
        out.add_params(MT.ARG_NUM_SAMPLES, n)
        out.add_params(MT.ARG_ROUND_IDX, self._secagg_round)
        self.send_message(out)

    def _on_recover(self, msg: Message):
        if self._secagg_party is None or msg.get(MT.ARG_ROUND_IDX) != self._secagg_round:
            return
        dropped = msg.get(MT.ARG_DROPPED)
        vec = self._secagg_party.recovery_mask(dropped)
        out = Message(MT.C2S_RECOVERY, self.rank, 0)
        out.add_params(MT.ARG_ROUND_IDX, self._secagg_round)
        out.add_params(MT.ARG_DROPPED, list(map(int, dropped)))
        out.add_params(MT.ARG_RECOVERY_VEC, vec)
        self.send_message(out)

    def _on_sync(self, msg: Message):
        self.trainer.update_dataset(msg.get(MT.ARG_CLIENT_INDEX))
        round_idx = msg.get(MT.ARG_ROUND_IDX)
        w_round = msg.get(MT.ARG_MODEL_PARAMS)
        if w_round is None:
            # quantized downlink: rebuild the broadcast model from the
            # codec-tagged payload. The decode template only supplies leaf
            # shapes and the treedef, so a fresh model.init works — the
            # decoded tree is byte-identical to the dequantized reference
            # the server kept as this round's global model.
            from fedml_tpu.core import compression as CZ

            payload = msg.get(MT.ARG_MODEL_QUANT)
            codec = msg.get(MT.ARG_MODEL_CODEC)
            if payload is None or codec is None:
                raise ValueError(
                    f"model sync for round {round_idx} carries neither "
                    "model_params nor a codec-tagged quantized payload"
                )
            if self._downlink_template is None:
                self._downlink_template = jax.device_get(
                    self.trainer.model.init(jax.random.PRNGKey(0))
                )
            w_round = CZ.decode_delta(payload, self._downlink_template, codec)
        fd = None
        if self._faults is not None:
            cid = int(self.trainer.client_index)
            fd = self._faults.decide(cid, int(round_idx))
            if fd.crashed:
                # the CLIENT is gone from crash_at_round on: no training,
                # no upload whenever it is sampled — the server's
                # deadline/quorum absorbs each missing upload; this worker
                # slot stays alive for the healthy clients later rounds
                # assign it (the injector records one crash per client)
                self._faults.record(cid, int(round_idx), "crash")
                return
            if fd.drop:
                # dropout: skip the round entirely (never uploads) — the
                # quorum path aggregates the partial cohort
                self._faults.record(cid, int(round_idx), "dropout")
                return
        t_train = time.perf_counter()
        weights, n = self.trainer.train(round_idx, w_round)
        if fd is not None and fd.slowdown_s:
            self._faults.record(
                int(self.trainer.client_index), int(round_idx), "slowdown",
                detail=fd.slowdown_s,
            )
            time.sleep(fd.slowdown_s)
        # beacon train time: compute INCLUDING any injected slowdown (a
        # slow device trains slowly — that is what the tier digests bin)
        train_s = time.perf_counter() - t_train
        comp = self.config.comm.compression
        if self.config.comm.secure_agg:
            # advertise a fresh per-round keypair; the masked upload waits
            # for the registry (_on_pubkeys). The secret key lives only in
            # this process's ClientParty.
            from fedml_tpu.secagg.secure_aggregation import (
                ClientParty,
                tree_dim,
            )

            self._secagg_party = ClientParty(self.rank - 1, tree_dim(weights))
            self._secagg_round = round_idx
            self._secagg_pending = (weights, w_round, n)
            adv = Message(MT.C2S_PUBKEY, self.rank, 0)
            adv.add_params(MT.ARG_ROUND_IDX, round_idx)
            adv.add_params(MT.ARG_PUBKEY, self._secagg_party.pk)
            self.send_message(adv)
            return
        from fedml_tpu.core import compression as CZ
        from fedml_tpu.telemetry import get_comm_meter

        out = Message(MT.C2S_SEND_MODEL, self.rank, 0)
        # fp32-equivalent cost of this update — the denominator of the
        # uplink byte-cut ratio (comm/uplink_* in summary.json); metered
        # for uncompressed uploads too so a baseline run carries the
        # same keys a quantized run is compared against. Counted
        # arithmetically (4 B × element count) — never by materializing
        # a cast copy of the tree on the hot upload path.
        raw_bytes = 4 * sum(
            int(np.size(a)) for a in jax.tree_util.tree_leaves(weights)
        )
        encode_s = 0.0
        if comp != "none":
            # uplink compression (core/compression.py): send the encoded
            # round delta; the server reconstructs against the same w_round
            t_enc = time.perf_counter()
            if self._ef is not None:
                payload = self._ef.encode(
                    self.trainer.client_index, weights, w_round
                )
            else:
                payload = CZ.encode_update(
                    weights, w_round, comp, self.config.comm.topk_frac
                )
            encode_s = time.perf_counter() - t_enc
            get_comm_meter().on_uplink(CZ.payload_bytes(payload), raw_bytes)
            out.add_params(MT.ARG_MODEL_DELTA, payload)
            out.add_params(MT.ARG_COMPRESSION, comp)
        else:
            # as-shipped payload = the leaves' actual buffer bytes (equal
            # to raw_bytes for fp32 weights, smaller for e.g. bf16)
            shipped = sum(
                int(a.nbytes) for a in jax.tree_util.tree_leaves(weights)
            )
            get_comm_meter().on_uplink(shipped, raw_bytes)
            out.add_params(MT.ARG_MODEL_PARAMS, weights)
        out.add_params(MT.ARG_NUM_SAMPLES, n)
        # round tag: lets the server discard a straggler's upload for an
        # already-closed round (FedConfig.deadline_s)
        out.add_params(MT.ARG_ROUND_IDX, round_idx)
        if self.trainer.last_loss is not None:
            out.add_params(MT.ARG_TRAIN_LOSS, float(self.trainer.last_loss))
        if getattr(self.config.comm, "beacons", True):
            # telemetry beacon (telemetry/wire.py): a bounded summary of
            # this round's local measurements, piggybacked on the upload.
            # Attached (and metered) ONCE — the flaky duplicate below
            # restates the same dict, and the server dedupes consumption
            # per (worker, round). Rides the envelope only: aggregation
            # never reads it, so numerics are identical with beacons off.
            from fedml_tpu.telemetry.wire import beacon_nbytes, build_beacon

            snap = get_comm_meter().snapshot()
            tier = None
            if self._faults is not None:
                plan = getattr(self._faults, "plan", None)
                if plan is not None:
                    tier = plan.tier_of(self.trainer.client_index)
            beacon = build_beacon(
                train_s=train_s,
                encode_s=encode_s,
                retries=sum(snap.get("send_retries", {}).values()),
                codec=comp,
                tier=tier,
            )
            out.add_params(MT.ARG_TELEMETRY, beacon)
            get_comm_meter().on_beacon(beacon_nbytes(beacon))
        self.send_message(out)
        if fd is not None and fd.flaky:
            # flaky upload = at-least-once double delivery; the sync
            # server's per-worker slot overwrite absorbs the duplicate
            self._faults.record(
                int(self.trainer.client_index), int(round_idx), "flaky"
            )
            try:
                self.send_message(out)
            except Exception:  # noqa: BLE001 — best-effort duplicate: the
                pass  # real upload above already landed


def run_federation(
    config: RunConfig,
    data: FederatedDataset,
    model: ModelDef,
    comm_factory,
    task: str = "classification",
    log_fn=None,
    trainer_factory=None,
    server_opt: bool = False,
    warmup: bool = False,
):
    """One-process federation over any transport: 1 server + K client actors
    in threads, each on ``comm_factory(rank)`` (a BaseCommManager) — the
    transport-path analog of the reference's mpirun smoke runs
    (CI-script-framework.sh:16-23), but with a real exit-code/join
    discipline, and pluggable across loopback/gRPC/MQTT exactly like the
    reference's ``--backend`` switch (client_manager.py:20-33). Returns the
    server manager (global_vars, history).

    One worker is spawned per scheduler slot — ``ceil(client_num_per_round
    * overprovision_factor)`` of them — and a FedConfig.fault_plan, if
    set, is parsed ONCE into a single FaultInjector shared by every
    client actor AND the server's stall valve (no repeat file reads, no
    plan-swapped-mid-startup drift); its counters land in summary.json
    and the server's health registry.

    ``warmup=True`` AOT-compiles the shared local-train program for every
    shape class the partition can produce BEFORE any worker thread starts
    — the warmup barrier that lets ``deadline_s`` rounds begin with
    compilation already paid instead of racing a cold compile, in every
    round (not just round 0 — partition_shape_classes in data/base.py is
    the enumeration contract).

    This is now a thin blocking wrapper over
    :class:`fedml_tpu.serve.FedSession` — the long-lived multi-tenant
    service runs N of these sessions concurrently in one process; this
    entry point keeps the classic one-shot semantics (and, having no
    TelemetryScope of its own, the process-global telemetry) intact."""
    from fedml_tpu.serve.session import FedSession

    return FedSession(
        config,
        data,
        model,
        algorithm="fedavg",
        comm_factory=comm_factory,
        task=task,
        log_fn=log_fn,
        trainer_factory=trainer_factory,
        server_opt=server_opt,
        warmup=warmup,
    ).run()


def run_loopback_federation(
    config: RunConfig,
    data: FederatedDataset,
    model: ModelDef,
    task: str = "classification",
    log_fn=None,
    server_opt: bool = False,
    warmup: bool = False,
):
    """Federation over the in-process loopback hub (see run_federation)."""
    hub = LoopbackHub()
    return run_federation(
        config,
        data,
        model,
        lambda rank: LoopbackCommManager(hub, rank),
        task=task,
        log_fn=log_fn,
        server_opt=server_opt,
        warmup=warmup,
    )


def run_shm_federation(
    config: RunConfig,
    data: FederatedDataset,
    model: ModelDef,
    task: str = "classification",
    log_fn=None,
    sock_dir: Optional[str] = None,
    server_opt: bool = False,
    warmup: bool = False,
    namespace: str = "",
):
    """Federation over the shared-memory local transport (TRPC-equivalent,
    ref trpc_comm_manager.py:25-114): bulk tensors ride POSIX shared memory,
    only tiny control records cross the per-rank UNIX sockets.

    ``namespace`` prefixes the socket names — REQUIRED to be unique per
    federation when two concurrent runs share an explicit ``sock_dir``
    (the serve path's sessions generate their own; see ShmCommManager)."""
    import tempfile

    from fedml_tpu.core.shm_comm import ShmCommManager

    with tempfile.TemporaryDirectory(prefix="fedml_shm_") as d:
        return run_federation(
            config,
            data,
            model,
            lambda rank: ShmCommManager(
                rank, sock_dir or d, namespace=namespace
            ),
            task=task,
            log_fn=log_fn,
            server_opt=server_opt,
            warmup=warmup,
        )


def run_mqtt_federation(
    config: RunConfig,
    data: FederatedDataset,
    model: ModelDef,
    task: str = "classification",
    log_fn=None,
    host: str = None,
    port: int = 1883,
    server_opt: bool = False,
    warmup: bool = False,
):
    """Federation over MQTT pub/sub (ref mqtt_comm_manager.py:14-123):
    embedded in-process broker by default, real broker when host given."""
    from fedml_tpu.core.mqtt_comm import EmbeddedBroker, MqttCommManager

    if host is None:
        broker = EmbeddedBroker()
        factory = lambda rank: MqttCommManager(rank, broker=broker)
    else:
        factory = lambda rank: MqttCommManager(rank, host=host, port=port)
    return run_federation(
        config, data, model, factory, task=task, log_fn=log_fn,
        server_opt=server_opt, warmup=warmup,
    )
