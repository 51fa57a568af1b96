"""Round flight recorder — a bounded per-round ring that folds the span
stream into one record per round.

The tracer (telemetry/spans.py) answers "show me every interval" — a
Perfetto file you read after the run. A long-lived federation service
needs the opposite shape: *the last K rounds, summarized, right now*.
The flight recorder subscribes to finished spans and folds each round's
lifecycle (``select`` / ``broadcast`` / ``local_train`` / ``aggregate``
/ ``eval`` — or ``server_step`` on the FedBuff path, which has no
rounds) into one compact record:

- phase wall seconds (summed per phase — K transport clients' parallel
  ``local_train`` spans also fold into p50/max straggler spread);
- comm deltas since the previous fold (bytes/messages/retries from the
  session's :class:`~fedml_tpu.telemetry.comm.CommMeter`);
- compile activity credited to the tenant via the recompile sentinel's
  scope attribution (``recompiles`` — nonzero mid-run means a shape
  class escaped warmup);
- cohort size and the straggler count from
  :class:`~fedml_tpu.telemetry.health.ClientHealthRegistry`.

**Bounded like the fault-event log** (PR-11's
``health_trace_budget_bytes``): the ring holds at most
``PopulationConfig.flight_rounds`` records AND at most
``flight_budget_bytes`` of them — whichever bound is tighter wins, so a
month-long tenant's recorder is O(K), never O(rounds). Rolling
percentiles (p50/p95 per phase over the ring) export as Prometheus
gauges (``fedml_flight_*``, tenant-labeled on the service /metrics) and
as a ``flight/*`` block in summary.json; the live tail serves the
``/tenants/<name>`` introspection endpoint (serve/introspect.py).

Wiring: :class:`~fedml_tpu.serve.session.FedSession` gives every tenant
one recorder on its :class:`~fedml_tpu.telemetry.scope.TelemetryScope`
(shared across supervised restarts — one tenant, one flight history);
the single-run CLI attaches one to the run tracer under
``--telemetry_dir``/``--prom_port`` and writes ``flight.json``."""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from fedml_tpu.telemetry.metrics import MetricsRegistry, get_registry
from fedml_tpu.telemetry.spans import SpanEvent, Tracer

# Phase spans folded into a record, in lifecycle order. "round" (sync) and
# "server_step" (FedBuff — it is both a phase and the fold trigger) are
# the record boundaries. forward/boundary/backward are the split/vertical
# runtimes' per-batch phases (fedml_tpu/splitfed/): client cut-layer
# forward, server top-half step at the wire boundary, client backward
# with the returned activation grads.
PHASES = ("select", "broadcast", "local_train", "forward", "boundary",
          "backward", "aggregate", "eval", "server_step")

# Conservative per-record footprint estimate against the byte budget: a
# folded record is a flat dict of ~20 scalar slots plus a small phases
# dict (measured ~450 B of JSON; the python-object footprint errs higher,
# so the estimate does too — the budget must bind before RSS does).
_RECORD_BYTES = 800

# Open (not yet folded) rounds kept at most — phase spans for a round the
# recorder never sees fold on must not accumulate (an abandoned round, a
# crashed attempt mid-round).
_MAX_PENDING = 16


def attached_recorder(tracer: Tracer) -> Optional["FlightRecorder"]:
    """The FlightRecorder already listening on ``tracer``, if any — so a
    FedSession whose ambient tracer carries the CLI's run recorder
    ADOPTS it instead of attaching a second one (every round would
    otherwise fold twice, and two recorders with different capacities
    would fight over the same global gauges)."""
    for fn in tracer.listeners():
        owner = getattr(fn, "__self__", None)
        if isinstance(owner, FlightRecorder):
            return owner
    return None


class FlightRecorder:
    """Fold the span stream into a bounded last-K-rounds ring."""

    def __init__(
        self,
        max_rounds: int = 64,
        budget_bytes: int = 64 << 10,
        registry: Optional[MetricsRegistry] = None,
        comm_meter=None,
        recompiles_fn: Optional[Callable[[], int]] = None,
        health=None,
        clock: Callable[[], float] = time.monotonic,
    ):
        by_budget = max(1, int(budget_bytes) // _RECORD_BYTES)
        self.capacity = max(1, min(int(max_rounds), by_budget))
        self._ring: deque = deque(maxlen=self.capacity)
        self._pending: Dict[int, dict] = {}
        # round indices folded before the current attempt (supervised
        # restarts): a re-run of round R must open a FRESH record, never
        # merge into the crashed attempt's partial one
        self._sealed: set = set()
        # rounds_folded at the last begin_attempt(): rounds_per_s only
        # counts the current attempt (the backoff gap must not skew it)
        self._attempt_fold_floor = 0
        self._lock = threading.Lock()
        self._listeners: List[Callable[[dict], None]] = []
        self._tracer: Optional[Tracer] = None
        self._clock = clock
        self.rounds_folded = 0
        self.comm_meter = comm_meter
        self.recompiles_fn = recompiles_fn
        self.health = health
        self._last_comm: Optional[dict] = None
        self._last_recompiles = 0
        self._last_fold_t: Optional[float] = None
        r = registry or get_registry()
        self._g_round = r.gauge(
            "fedml_flight_round_seconds",
            "Rolling round wall-time percentiles over the flight ring",
            ("q",),
        )
        self._g_phase = r.gauge(
            "fedml_flight_phase_seconds",
            "Rolling per-phase wall-time percentiles over the flight ring",
            ("phase", "q"),
        )
        self._g_folded = r.gauge(
            "fedml_flight_rounds_folded",
            "Rounds the flight recorder has folded (ring keeps the last K)",
        )

    @classmethod
    def from_config(cls, config, **kw) -> "FlightRecorder":
        """Build with the run's population bounds
        (PopulationConfig.flight_rounds / .flight_budget_bytes) — the one
        definition every runtime shares, like
        ``ClientHealthRegistry.from_config``."""
        pop = getattr(config, "population", None)
        if pop is not None:
            kw.setdefault("max_rounds", pop.flight_rounds)
            kw.setdefault("budget_bytes", pop.flight_budget_bytes)
        return cls(**kw)

    # -- span-stream feeding -------------------------------------------------

    def attach(self, tracer: Tracer) -> "FlightRecorder":
        """Feed from the span stream. Idempotent per tracer; switching
        tracers detaches from the previous one first (same contract as
        ``ClientHealthRegistry.attach``)."""
        if self._tracer is tracer:
            return self
        self.detach()
        tracer.add_listener(self._on_span)
        self._tracer = tracer
        if self._last_comm is None and self.comm_meter is not None:
            self._last_comm = self._comm_totals()
        return self

    def detach(self) -> None:
        if self._tracer is not None:
            self._tracer.remove_listener(self._on_span)
            self._tracer = None

    def begin_attempt(self) -> None:
        """Fence for supervised restarts (one recorder per tenant scope,
        reused across attempts): drop the crashed attempt's half-open
        rounds and SEAL every already-folded record — a restarted round
        R re-runs from its checkpoint, and its phase spans must open a
        fresh record instead of merging into (and corrupting) the dead
        attempt's partial one, which stays in the ring as crash
        history. Idempotent; a fresh recorder's fence is empty."""
        with self._lock:
            self._pending.clear()
            self._sealed = {rec["round"] for rec in self._ring}
            self._attempt_fold_floor = self.rounds_folded

    def add_listener(self, fn: Callable[[dict], None]) -> None:
        """Call ``fn(record)`` after every fold (the SLO watchdog hook).
        Listener errors are contained, like the tracer's own."""
        with self._lock:
            if fn not in self._listeners:
                self._listeners.append(fn)

    def remove_listener(self, fn: Callable[[dict], None]) -> None:
        with self._lock:
            if fn in self._listeners:
                self._listeners.remove(fn)

    def _on_span(self, ev: SpanEvent) -> None:
        name = ev.name
        if name == "round":
            key = ev.attrs.get("round")
            if key is None:
                return
            self._fold(int(key), ev.dur_us / 1e6, ev.attrs)
            return
        if name not in PHASES:
            return
        # FedBuff server_step spans carry "version", not "round" — and
        # each IS a full record (async has no round lifecycle around it)
        key = ev.attrs.get("round")
        if key is None and name == "server_step":
            key = ev.attrs.get("version")
        if key is None:
            return
        key = int(key)
        dur_s = ev.dur_us / 1e6
        folded = None
        with self._lock:
            p = self._pending.get(key)
            if p is None:
                if self._merge_late_locked(key, name, dur_s):
                    return
                p = self._pending[key] = {"phases": {}, "train": []}
                while len(self._pending) > _MAX_PENDING:
                    # oldest open round is abandoned — drop it
                    self._pending.pop(next(iter(self._pending)))
            p["phases"][name] = p["phases"].get(name, 0.0) + dur_s
            if name == "local_train":
                t = p["train"]
                if len(t) < 1024:  # bounded straggler-spread window
                    t.append(dur_s)
            clients = ev.attrs.get("clients", ev.attrs.get("n_uploads"))
            if clients is not None:
                p["clients"] = int(clients)
        if name == "server_step":
            folded = self._fold(key, dur_s, ev.attrs)
        return folded

    def observe_beacon(
        self,
        round_idx: int,
        train_s: float,
        encode_s: float = 0.0,
        wire_s: float = 0.0,
    ) -> None:
        """Fold one client telemetry beacon (telemetry/wire.py) into the
        round's record: MEASURED client-side train/encode seconds plus the
        residual wire+queue time the server derives (rtt - train - encode)
        — the train-vs-wire-vs-queue split a remote fleet cannot get from
        the server's own spans. Kept under a separate ``beacon`` key, so
        in-process runs (where local_train spans already feed phases)
        never double-count."""
        key = int(round_idx)
        add = {
            "n": 1,
            "train_s": max(0.0, float(train_s)),
            "encode_s": max(0.0, float(encode_s)),
            "wire_s": max(0.0, float(wire_s)),
        }
        with self._lock:
            p = self._pending.get(key)
            if p is None:
                # round already folded (async arrival): merge into the
                # ring record unless sealed/evicted — same contract as
                # late phase spans
                if self.rounds_folded and key not in self._sealed:
                    for rec in reversed(self._ring):
                        if rec["round"] == key:
                            self._beacon_accumulate(
                                rec.setdefault(
                                    "beacon",
                                    {
                                        "n": 0,
                                        "train_s": 0.0,
                                        "encode_s": 0.0,
                                        "wire_s": 0.0,
                                    },
                                ),
                                add,
                            )
                            return
                    if self._ring and key <= self._ring[-1]["round"]:
                        return  # evicted history: drop, never reopen
                p = self._pending[key] = {"phases": {}, "train": []}
                while len(self._pending) > _MAX_PENDING:
                    self._pending.pop(next(iter(self._pending)))
            b = p.setdefault(
                "beacon",
                {"n": 0, "train_s": 0.0, "encode_s": 0.0, "wire_s": 0.0},
            )
            self._beacon_accumulate(b, add)

    @staticmethod
    def _beacon_accumulate(into: dict, add: dict) -> None:
        into["n"] += add["n"]
        for k in ("train_s", "encode_s", "wire_s"):
            into[k] = round(into[k] + add[k], 6)

    def _merge_late_locked(self, key: int, name: str, dur_s: float) -> bool:
        """A phase span arriving after its round folded (the sim's eval
        runs from the deferred metrics-log path): merge into the ring
        record if the round is still there. Caller holds the lock.
        Returns True when handled (merged or staler than the ring).
        Records sealed by :meth:`begin_attempt` never receive merges —
        a supervised re-run of that round opens a fresh record."""
        if not self.rounds_folded or key in self._sealed:
            return False
        for rec in reversed(self._ring):
            if rec["round"] == key:
                rec["phases"][name] = rec["phases"].get(name, 0.0) + round(
                    dur_s, 6
                )
                return True
        # folded and already evicted, or from a round older than anything
        # pending — either way it cannot open a new pending slot
        return key <= self._ring[-1]["round"] if self._ring else False

    # -- folding -------------------------------------------------------------

    def _comm_totals(self) -> dict:
        snap = self.comm_meter.snapshot()
        return {
            "bytes_sent": sum(snap["bytes_sent"].values()),
            "bytes_received": sum(snap["bytes_received"].values()),
            "messages_sent": sum(snap["messages_sent"].values()),
            "retries": sum(snap.get("send_retries", {}).values()),
        }

    def _fold(self, key: int, wall_s: float, attrs: dict) -> dict:
        now = self._clock()
        comm = recompiles = None
        if self.comm_meter is not None:
            totals = self._comm_totals()
            base = self._last_comm or {}
            comm = {k: v - base.get(k, 0) for k, v in totals.items()}
            self._last_comm = totals
        if self.recompiles_fn is not None:
            try:
                total = int(self.recompiles_fn())
            except Exception:  # noqa: BLE001 — attribution is best-effort
                total = self._last_recompiles
            recompiles = max(0, total - self._last_recompiles)
            self._last_recompiles = total
        stragglers = fleet = None
        if self.health is not None:
            try:
                stragglers = len(self.health.straggler_ids())
                # the straggler set is FLEET-wide — record the matching
                # denominator so consumers never divide it by the
                # (smaller) per-round cohort
                fleet = self.health.known_client_count()
            except Exception:  # noqa: BLE001
                stragglers = fleet = None
        with self._lock:
            p = self._pending.pop(key, {"phases": {}, "train": []})
            train = sorted(p.get("train", ()))
            rec = {
                "round": key,
                "t_s": round(wall_s, 6),
                "ts": now,
                "phases": {
                    n: round(s, 6) for n, s in p.get("phases", {}).items()
                },
                "clients": p.get("clients", attrs.get("clients")),
                "train_n": len(train),
                "train_p50_s": (
                    round(train[len(train) // 2], 6) if train else None
                ),
                "train_max_s": round(train[-1], 6) if train else None,
                "stragglers": stragglers,
                "clients_seen": fleet,
            }
            if attrs.get("overlap_s") is not None:
                # host prep for the NEXT round that hid behind this round's
                # device work (FedConfig.pipeline) — recorded additively:
                # t_s stays the round's true wall clock, overlap_s is the
                # host time the pipeline kept OFF the critical path
                rec["overlap_s"] = float(attrs["overlap_s"])
                rec["pipeline_depth"] = int(attrs.get("pipeline_depth", 1))
            if p.get("beacon"):
                rec["beacon"] = p["beacon"]
            if comm is not None:
                rec["comm_bytes_sent"] = comm["bytes_sent"]
                rec["comm_bytes_received"] = comm["bytes_received"]
                rec["comm_messages"] = comm["messages_sent"]
                rec["comm_retries"] = comm["retries"]
            if recompiles is not None:
                rec["recompiles"] = recompiles
            self._ring.append(rec)
            # the freshly-folded record is the mergeable one for this
            # round index again (a restarted round re-folds under a key
            # begin_attempt sealed)
            self._sealed.discard(key)
            self.rounds_folded += 1
            self._last_fold_t = now
            listeners = list(self._listeners)
            pct = self._percentiles_locked()
        self._export_gauges(pct)
        for fn in listeners:
            try:
                fn(rec)
            except Exception:  # noqa: BLE001 — a listener must never
                # break the span stream (same contract as the tracer's)
                import logging

                logging.exception("flight-recorder listener failed")
        return rec

    def _export_gauges(self, pct: dict) -> None:
        self._g_folded.set(self.rounds_folded)
        for q, v in pct.get("round", {}).items():
            self._g_round.set(v, q=q)
        for phase, qs in pct.items():
            if phase == "round":
                continue
            for q, v in qs.items():
                self._g_phase.set(v, phase=phase, q=q)

    # -- queries -------------------------------------------------------------

    @staticmethod
    def _pctl(xs: List[float], q: float) -> float:
        xs = sorted(xs)
        return round(xs[min(int(q * len(xs)), len(xs) - 1)], 6)

    def _percentiles_locked(self) -> dict:
        out: Dict[str, dict] = {}
        walls = [r["t_s"] for r in self._ring]
        if walls:
            out["round"] = {
                "p50": self._pctl(walls, 0.5), "p95": self._pctl(walls, 0.95)
            }
        per_phase: Dict[str, List[float]] = {}
        for r in self._ring:
            for n, s in r["phases"].items():
                per_phase.setdefault(n, []).append(s)
        for n, xs in per_phase.items():
            out[n] = {"p50": self._pctl(xs, 0.5), "p95": self._pctl(xs, 0.95)}
        return out

    def percentiles(self) -> dict:
        """{"round": {"p50", "p95"}, "<phase>": {...}} over the ring."""
        with self._lock:
            return self._percentiles_locked()

    def size(self) -> int:
        """Records currently in the ring — the cheap length accessor for
        per-fold consumers (``tail()`` deep-copies every record)."""
        with self._lock:
            return len(self._ring)

    def tail(self, n: Optional[int] = None) -> List[dict]:
        """The last ``n`` folded records (all of the ring by default),
        oldest first, JSON-ready copies."""
        with self._lock:
            # copy INSIDE the lock: _merge_late_locked mutates ring
            # records' phases dicts in place, and an iteration racing
            # that insert raises mid-scrape
            recs = [self._copy_rec(r) for r in self._ring]
        if n is not None:
            recs = recs[-int(n):]
        return recs

    @staticmethod
    def _copy_rec(r: dict) -> dict:
        out = dict(r, phases=dict(r["phases"]))
        if "beacon" in r:
            out["beacon"] = dict(r["beacon"])
        return out

    def last(self) -> Optional[dict]:
        with self._lock:
            if not self._ring:
                return None
            return self._copy_rec(self._ring[-1])

    def last_fold_age_s(self) -> Optional[float]:
        """Seconds since the last fold (the /status "current round age")
        — None before the first round completes."""
        with self._lock:
            if self._last_fold_t is None:
                return None
            return max(0.0, self._clock() - self._last_fold_t)

    def rounds_per_s(self) -> Optional[float]:
        """Rolling throughput over the CURRENT attempt's fold timestamps
        (None until the attempt has folded two records). Records from
        before :meth:`begin_attempt` are excluded — spanning the crash +
        backoff gap would depress the rate and fire spurious
        ``slo_min_rounds_per_s`` breaches after every restart."""
        with self._lock:
            n = min(
                len(self._ring),
                self.rounds_folded - self._attempt_fold_floor,
            )
            if n < 2:
                return None
            recs = list(self._ring)[-n:]
            span = recs[-1]["ts"] - recs[0]["ts"]
            if span <= 0:
                return None
            return (n - 1) / span

    def approx_bytes(self) -> int:
        """The ring's budget-accounted footprint (estimate, errs high)."""
        with self._lock:
            return len(self._ring) * _RECORD_BYTES

    def summary_row(self) -> dict:
        """Flat ``{"flight/...": value}`` MetricsLogger row — summary.json
        stays the single CI oracle."""
        with self._lock:
            recs = list(self._ring)
            folded = self.rounds_folded
            pct = self._percentiles_locked()
        row = {
            "flight/rounds_folded": folded,
            "flight/ring_capacity": self.capacity,
        }
        for name, qs in pct.items():
            row[f"flight/p50_{name}_s"] = qs["p50"]
            row[f"flight/p95_{name}_s"] = qs["p95"]
        if recs:
            last = recs[-1]
            if last.get("stragglers") is not None:
                row["flight/stragglers_last"] = last["stragglers"]
            bytes_rows = [
                r["comm_bytes_sent"] for r in recs if "comm_bytes_sent" in r
            ]
            if bytes_rows:
                row["flight/comm_bytes_per_round"] = round(
                    sum(bytes_rows) / len(bytes_rows), 1
                )
            recompile_rows = [
                r["recompiles"] for r in recs if "recompiles" in r
            ]
            if recompile_rows:
                row["flight/recompiles_in_ring"] = sum(recompile_rows)
            overlap_rows = [
                r["overlap_s"] for r in recs if "overlap_s" in r
            ]
            if overlap_rows:
                # total host time the round pipeline hid behind device
                # work, and how many ring rounds were prepared ahead —
                # the ci gate's measured evidence that overlap happened
                row["flight/overlap_s"] = round(sum(overlap_rows), 6)
                row["flight/pipelined_rounds"] = len(overlap_rows)
        rate = self.rounds_per_s()
        if rate is not None:
            row["flight/rounds_per_s"] = round(rate, 3)
        return row
