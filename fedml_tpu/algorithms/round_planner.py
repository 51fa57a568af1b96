"""Measured fused-vs-eager round planner.

The fused multi-round scan (``make_fedavg_multiround``) exists to
amortize per-round host dispatch; whether it actually WINS depends on
the model, the backend, and everything the compile runtime has since
changed about the eager path's cost (the round-5 record had the fused
north-star row 36% SLOWER than eager — the config heuristic "fuse
whenever ``fused_rounds > 1``" had gone stale). This module replaces
that heuristic with a measurement: under ``FedConfig.fused_plan =
"measured"``, the first rounds of a run probe BOTH schedules and the
planner commits to the measured winner, per

    (algorithm, steps-class, batch-size, cohort-size)

— the tuple that determines the programs both schedules dispatch.

The probe reads its per-round costs from the PR-12 flight recorder
(telemetry/flight.py), not from new instrumentation: probed segments are
executed with an explicit device sync inside their ``round`` span (the
ordinary async dispatch makes an unsynced span measure host dispatch
only), so the folded record's wall IS the honest schedule cost — a
fused chunk's record carries ``fused_rounds`` and divides down to
per-round. Each arm keeps its best (min) observed per-round cost:
minimum-of-K is the standard microbenchmark statistic, robust to a
compile-tainted first sample and to host noise, and — decisive for the
test contract — a DETERMINISTIC function of the observed records: the
same flight history always commits the same schedule. Ties break toward
fused (it amortizes dispatch; with measured costs equal, fewer
dispatches is the better bet).

After every active key has committed, the planner detaches from the
recorder (and detaches the recorder from the tracer when the planner
created it privately) — steady-state rounds carry zero probe overhead
and the span stream has no extra listener.

The committed decision and both arms' measured costs land in
summary.json under ``flight/planner_*`` / ``flight/probe_*`` keys
(docs/OBSERVABILITY.md) — the ci.sh fused-vs-eager gate reads the
winner off those, never off a config echo."""

from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, Optional

# Folded records per arm before committing. Two suffice: the first
# sample of an arm may carry a lazy compile or cold cache effects; the
# min over two keeps the clean one.
PROBE_SAMPLES = 2


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """What determines the programs both schedules would dispatch."""

    algo: str
    steps: int
    bs: int
    cohort: int

    def label(self) -> str:
        return f"{self.algo}:s{self.steps}b{self.bs}c{self.cohort}"


class _KeyState:
    __slots__ = ("fused", "eager", "decision")

    def __init__(self):
        self.fused: list = []  # per-round seconds, fused arm
        self.eager: list = []  # per-round seconds, eager arm
        self.decision: Optional[str] = None


class SchedulePlanner:
    """Probe-then-commit schedule selection over flight-recorder folds.

    Wiring (FedAvgAPI): ``plan(key, round_idx, fusible_len)`` replaces
    the tail of ``_fused_chunk_len`` — it returns the chunk length to
    run (``fusible_len`` for the fused arm / a committed fused decision,
    1 for the eager arm / a committed eager decision) and is idempotent
    per ``round_idx`` (warmup and the train loop both consult it).
    ``wants_sync(round_idx)`` tells the train loop to block on the
    device inside the round span, so the fold measures schedule cost,
    not dispatch cost."""

    def __init__(self, log_fn: Optional[Callable[[dict], None]] = None):
        self._lock = threading.Lock()
        self._states: Dict[PlanKey, _KeyState] = {}
        # probe segments in flight: start round -> (key, arm, length)
        self._pending: Dict[int, tuple] = {}
        # idempotence: round -> planned chunk length (warmup + train both
        # ask; the answer must not depend on how often they ask)
        self._planned: Dict[int, int] = {}
        self._log_fn = log_fn
        self._recorder = None
        self._tracer = None
        self._owns_recorder = False
        self._detached = False

    # -- wiring --------------------------------------------------------------

    def attach(self, tracer, config=None) -> "SchedulePlanner":
        """Listen on ``tracer``'s flight recorder, adopting an ambient
        one (the CLI's ``--telemetry_dir``/serve-layer recorder) or
        attaching a private one — the probe reads MEASURED phase folds
        either way, it never re-instruments."""
        from fedml_tpu.telemetry.flight import FlightRecorder, attached_recorder

        rec = attached_recorder(tracer)
        if rec is None:
            rec = (
                FlightRecorder.from_config(config)
                if config is not None
                else FlightRecorder()
            )
            rec.attach(tracer)
            self._owns_recorder = True
        self._recorder = rec
        self._tracer = tracer
        rec.add_listener(self.observe)
        self._detached = False
        return self

    def close(self) -> None:
        """Stop listening (idempotent). Called automatically once every
        probed key has committed."""
        if self._recorder is not None and not self._detached:
            self._recorder.remove_listener(self.observe)
            if self._owns_recorder:
                self._recorder.detach()
            self._detached = True
        with self._lock:
            # probe bookkeeping is dead once every key committed — the
            # steady state must hold zero per-round memory
            self._planned.clear()

    # -- the planning surface ------------------------------------------------

    def plan(self, key: PlanKey, round_idx: int, fusible_len: int) -> int:
        """Chunk length for the segment starting at ``round_idx``, given
        the structural planner allows ``fusible_len`` fused rounds."""
        r = int(round_idx)
        reattach = False
        try:
            with self._lock:
                cached = self._planned.get(r)
                if cached is not None:
                    return min(cached, fusible_len) if cached > 1 else cached
                st = self._states.get(key)
                if st is None:
                    st = self._states[key] = _KeyState()
                    # a NEW key after the probe closed (mid-run cohort or
                    # steps-class change): re-subscribe so its folds are
                    # observed — otherwise its probe segments would hang
                    # in _pending forever and the key could never commit
                    reattach = self._detached and self._tracer is not None
                return self._plan_locked(st, key, r, fusible_len)
        finally:
            if reattach:
                self.attach(self._tracer)

    def _plan_locked(
        self, st: "_KeyState", key: PlanKey, r: int, fusible_len: int
    ) -> int:
        if st.decision is not None:
            # committed: the answer is a pure function of the
            # decision — nothing to memoize (the _planned cache is
            # for the probe phase only; caching here would grow one
            # entry per round for the run's whole life)
            return fusible_len if st.decision == "fused" else 1
        # probe: fill the fused arm first (its samples are chunks —
        # fewer, costlier), then the eager arm, then commit
        in_flight_f = sum(
            1 for k, a, _ in self._pending.values()
            if k == key and a == "fused"
        )
        in_flight_e = sum(
            1 for k, a, _ in self._pending.values()
            if k == key and a == "eager"
        )
        if len(st.fused) + in_flight_f < PROBE_SAMPLES:
            arm, L = "fused", fusible_len
        elif len(st.eager) + in_flight_e < PROBE_SAMPLES:
            arm, L = "eager", 1
        else:
            # both arms fully scheduled but not yet folded (a caller
            # planning ahead of execution): run fused — the probe
            # decides retroactively, and fused is the amortizing
            # default while undecided. Not a probe segment.
            self._planned[r] = fusible_len
            return fusible_len
        self._pending[r] = (key, arm, L)
        self._planned[r] = L
        return L

    def wants_sync(self, round_idx: int) -> bool:
        """True when the segment starting at ``round_idx`` is a probe —
        the train loop must block on the device inside the round span so
        the folded wall measures the schedule, not the dispatch."""
        with self._lock:
            return int(round_idx) in self._pending

    def decision(self, key: PlanKey) -> Optional[str]:
        with self._lock:
            st = self._states.get(key)
            return st.decision if st is not None else None

    # -- fold feedback -------------------------------------------------------

    def observe(self, rec: dict) -> None:
        """Flight-recorder fold listener. Attributes probe records to
        their arm and commits a key once both arms have
        :data:`PROBE_SAMPLES` samples. Pure in the record stream — the
        same history always yields the same decisions (test contract)."""
        row = None
        with self._lock:
            seg = self._pending.pop(int(rec.get("round", -1)), None)
            if seg is None:
                return
            key, arm, L = seg
            st = self._states.get(key)
            if st is None or st.decision is not None:
                return
            per_round = float(rec["t_s"]) / max(
                int(rec.get("fused_rounds", 1)), 1
            )
            (st.fused if arm == "fused" else st.eager).append(per_round)
            if (
                len(st.fused) >= PROBE_SAMPLES
                and len(st.eager) >= PROBE_SAMPLES
            ):
                fused_s, eager_s = min(st.fused), min(st.eager)
                # tie → fused: equal measured cost, fewer dispatches
                st.decision = "fused" if fused_s <= eager_s else "eager"
                row = {
                    "flight/planner_schedule": st.decision,
                    "flight/planner_key": key.label(),
                    "flight/probe_fused_per_round_s": round(fused_s, 6),
                    "flight/probe_eager_per_round_s": round(eager_s, 6),
                    "flight/planner_probe_rounds": len(st.fused)
                    + len(st.eager),
                }
            done = not self._pending and all(
                s.decision is not None for s in self._states.values()
            )
        if row is not None and self._log_fn is not None:
            self._log_fn(row)
        if row is not None and done:
            # every active key committed — the probe is over; stop
            # taxing the span stream
            self.close()

    # -- introspection -------------------------------------------------------

    def summary_row(self) -> dict:
        """Flat ``flight/planner_*`` row of the latest state (the commit
        itself already logged through ``log_fn``; this is the pull-side
        surface for bench/tests)."""
        with self._lock:
            row: dict = {}
            for key, st in self._states.items():
                if st.decision is None:
                    continue
                row.setdefault("flight/planner_schedule", st.decision)
                row.setdefault("flight/planner_key", key.label())
                if st.fused:
                    row.setdefault(
                        "flight/probe_fused_per_round_s",
                        round(min(st.fused), 6),
                    )
                if st.eager:
                    row.setdefault(
                        "flight/probe_eager_per_round_s",
                        round(min(st.eager), 6),
                    )
            row["flight/planner_keys"] = len(self._states)
            return row
