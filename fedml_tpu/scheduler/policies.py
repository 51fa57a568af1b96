"""Selection policies + the ClientScheduler driver.

The reference samples cohorts with exactly one rule — ``np.random.seed(
round_idx)`` then ``choice`` without replacement (FedAVGAggregator.py:
80-88). That rule survives here verbatim as the ``uniform`` policy (its
parity is pinned by tests/test_fedavg_oracle.py), and everything else is
the scheduling layer the reference never had:

- ``weighted`` — inclusion probability proportional to local sample
  counts (larger shards carry more of the average; sampling them more
  often reduces aggregate variance at fixed k).
- ``power_of_choice`` — the loss-biased d-choose-k rule of Cho et al.
  2020: draw a candidate set of ``d = ceil(candidate_factor * k)``
  clients (size-weighted), then keep the k with the highest last-known
  local loss. Clients with no known loss rank as +inf, so cold clients
  are explored before the bias kicks in.
- ``straggler_aware`` — uniform over the clients the telemetry
  :class:`~fedml_tpu.telemetry.health.ClientHealthRegistry` does NOT
  currently flag as stragglers (the hook PR 1 shipped for exactly this),
  topping back up from the flagged set only when too few fast clients
  remain.
- ``overprovision`` — a wrapper around any policy that selects
  ``ceil(k * factor)`` clients, so a deadline/quorum round
  (FedConfig.deadline_s/min_clients) still closes with ~k useful uploads
  when some of the cohort drops.

Every policy is **round-keyed and seed-deterministic**: the draw is a
pure function of (seed, round_idx, policy inputs), never of call order or
process state — the vmap simulator and the transport federations must
select byte-identical cohorts from the same config, and a resumed run
must be able to re-derive its in-flight cohort.

Population scale (fedml_tpu/population/, docs/POPULATION.md): at/above
``PopulationConfig.ocohort_threshold`` clients the non-uniform policies
switch to O(cohort) draws — an alias table for ``weighted`` and the
power_of_choice candidate pool, rejection sampling for
``straggler_aware``'s exclusion draw — built once per run from the
:class:`~fedml_tpu.population.PopulationIndex` and never touching all N
per round. The switch is keyed on population size ONLY (identical in
the simulator and every transport, so sim/transport cohort parity is
preserved by construction); below the threshold the legacy exact numpy
draws run byte-for-byte. ``uniform`` stays the reference-parity
round-seeded draw at every scale — its O(N) permutation is the parity
contract itself.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class SelectionContext:
    """Everything a policy may consult beyond (round, k). All optional:
    a policy degrades gracefully (documented per policy) when its signal
    is missing rather than erroring — the transport server, the vmap
    simulator, and bare helpers construct different subsets of this."""

    seed: int = 0
    num_clients: int = 0
    # per-client local dataset sizes, indexed by client id (weighted /
    # power_of_choice candidate draw)
    sample_counts: Optional[np.ndarray] = None
    # last reported local train loss per client id (power_of_choice)
    losses: Optional[Dict[int, float]] = None
    # ClientHealthRegistry-shaped object (straggler_aware); only
    # .straggler_ids() is required
    health: Optional[object] = None
    # population.PopulationIndex for the O(cohort) draws; built lazily
    # from sample_counts at/above ocohort_threshold when absent
    index: Optional[object] = None
    ocohort_threshold: int = 65536


def _population_index(ctx: SelectionContext):
    """The context's PopulationIndex when the O(cohort) sampling paths
    should engage — explicit index, or lazily built from the packed
    counts once the population crosses the threshold. Returns None below
    the threshold (legacy exact draws) or when no counts exist."""
    if ctx.index is not None:
        return ctx.index
    if (
        ctx.sample_counts is not None
        and ctx.num_clients >= ctx.ocohort_threshold
        and len(ctx.sample_counts) == ctx.num_clients
    ):
        from fedml_tpu.population import PopulationIndex

        ctx.index = PopulationIndex(np.asarray(ctx.sample_counts, np.int64))
        return ctx.index
    return None


def _weighted_cohort(ctx: SelectionContext, rng, n: int, size: int) -> np.ndarray:
    """THE size-weighted distinct draw both weighted selection and the
    power_of_choice candidate pool use: the alias table's O(cohort)
    rejection draw at population scale, the legacy exact numpy draw
    below it. Distributionally identical (discarding duplicates from a
    with-replacement categorical stream IS sequential sampling without
    replacement); only the random stream differs, which is why the
    switch is population-keyed, never data-keyed."""
    pop = _population_index(ctx)
    if (
        pop is not None
        and pop.num_clients == n
        and pop.total_samples() > 0
    ):
        return pop.alias_table().draw_distinct(rng, size)
    return _weighted_draw(rng, n, size, _size_probs(ctx))


def _rng(ctx: SelectionContext, round_idx: int, salt: int = 0):
    """The one derivation of a policy's per-round RNG: a SeedSequence over
    (seed, round, salt) — independent of call order, identical across
    processes."""
    return np.random.default_rng([int(ctx.seed) & 0x7FFFFFFF, int(round_idx), int(salt)])


def _size_probs(ctx: SelectionContext) -> Optional[np.ndarray]:
    if ctx.sample_counts is None:
        return None
    c = np.asarray(ctx.sample_counts, np.float64)
    if len(c) != ctx.num_clients or c.sum() <= 0:
        return None
    return c / c.sum()


def _weighted_draw(rng, n: int, size: int, p: Optional[np.ndarray]) -> np.ndarray:
    """``rng.choice(n, size, replace=False, p=p)`` that tolerates
    zero-weight entries: numpy refuses to draw more items than p has
    non-zero entries (a zero-sample client shard — possible under the
    Dirichlet non-IID partitioner — would crash a weighted draw mid-run).
    When the request exceeds the non-zero support, every weighted client
    is taken and the remainder fills uniformly from the zero-weight ones."""
    if p is None:
        return rng.choice(n, size=size, replace=False)
    nz = np.flatnonzero(p)
    if size <= len(nz):
        return rng.choice(n, size=size, replace=False, p=p)
    zeros = np.setdiff1d(np.arange(n), nz)
    fill = rng.choice(zeros, size=size - len(nz), replace=False)
    return np.concatenate([rng.permutation(nz), fill])


class SelectionPolicy:
    """One cohort-selection rule. ``select`` must be a pure function of
    its arguments (round-keyed, seed-deterministic) and return a 1-D
    int array of distinct client ids of length ``min(k, num_clients)``."""

    name = "base"

    def select(self, round_idx: int, k: int, ctx: SelectionContext) -> np.ndarray:
        raise NotImplementedError

    def __repr__(self):
        return f"<{type(self).__name__} {self.name!r}>"


_POLICIES: Dict[str, Callable[..., SelectionPolicy]] = {}


def register_policy(name: str):
    """Register a policy factory under ``name`` (decorator)."""

    def deco(factory):
        _POLICIES[name] = factory
        return factory

    return deco


def get_policy(name: str, **kw) -> SelectionPolicy:
    try:
        factory = _POLICIES[name]
    except KeyError:
        raise ValueError(
            f"unknown selection policy {name!r}; registered: "
            f"{sorted(_POLICIES)}"
        ) from None
    return factory(**kw)


@register_policy("uniform")
class UniformPolicy(SelectionPolicy):
    """Reference-parity uniform draw: ``np.random.seed(round_idx)`` then
    ``choice`` without replacement (FedAVGAggregator.py:80-88). NOTE this
    deliberately ignores the run seed — runs with different seeds sample
    the same cohorts, exactly like the reference (pinned by
    tests/test_fedavg_oracle.py::test_client_sampling_parity)."""

    name = "uniform"

    def select(self, round_idx: int, k: int, ctx: SelectionContext) -> np.ndarray:
        n = ctx.num_clients
        if k > n:
            raise ValueError(
                f"client_num_per_round={k} exceeds client_num_in_total={n}"
            )
        if n == k:
            return np.arange(n)
        np.random.seed(round_idx)
        return np.random.choice(range(n), k, replace=False)


@register_policy("weighted")
class WeightedPolicy(SelectionPolicy):
    """Inclusion probability proportional to local sample counts. Falls
    back to a (seeded) uniform draw when the context carries no counts."""

    name = "weighted"

    def select(self, round_idx: int, k: int, ctx: SelectionContext) -> np.ndarray:
        n = ctx.num_clients
        k = min(k, n)
        rng = _rng(ctx, round_idx, salt=1)
        return _weighted_cohort(ctx, rng, n, k)


@register_policy("power_of_choice")
class PowerOfChoicePolicy(SelectionPolicy):
    """Loss-biased d-choose-k (Power-of-Choice, Cho et al. 2020): draw
    ``d = ceil(candidate_factor * k)`` candidates size-weighted, keep the
    k with the highest last-known local loss. Unknown losses rank as +inf
    (cold clients are explored first); ties break on a seeded per-round
    permutation, so the rule stays deterministic given (seed, round,
    loss map)."""

    name = "power_of_choice"

    def __init__(self, candidate_factor: float = 2.0):
        if candidate_factor < 1.0:
            raise ValueError("candidate_factor must be >= 1.0")
        self.candidate_factor = float(candidate_factor)

    def select(self, round_idx: int, k: int, ctx: SelectionContext) -> np.ndarray:
        n = ctx.num_clients
        k = min(k, n)
        d = min(n, max(k, int(math.ceil(self.candidate_factor * k))))
        rng = _rng(ctx, round_idx, salt=2)
        candidates = _weighted_cohort(ctx, rng, n, d)
        losses = ctx.losses or {}
        loss_of = lambda c: losses.get(int(c), math.inf)
        tiebreak = rng.permutation(d)
        order = sorted(
            range(d), key=lambda i: (-loss_of(candidates[i]), tiebreak[i])
        )
        return np.asarray([int(candidates[i]) for i in order[:k]], np.int64)


@register_policy("straggler_aware")
class StragglerAwarePolicy(SelectionPolicy):
    """Uniform over the clients the health registry does not flag as
    stragglers (telemetry.health.ClientHealthRegistry.straggler_ids —
    sliding-window slowest decile AND materially slower than the fleet).
    When fewer than k fast clients exist, the cohort tops back up from
    the flagged set (deterministically, by id) rather than shrinking —
    participation guarantees beat straggler avoidance. With no registry
    attached this is a seeded uniform draw."""

    name = "straggler_aware"

    def select(self, round_idx: int, k: int, ctx: SelectionContext) -> np.ndarray:
        n = ctx.num_clients
        k = min(k, n)
        rng = _rng(ctx, round_idx, salt=3)
        flagged: List[int] = []
        if ctx.health is not None:
            flagged = [c for c in ctx.health.straggler_ids() if c < n]
        if n >= ctx.ocohort_threshold:
            # O(cohort) form: rejection-sample the uniform draw instead
            # of materializing the O(N) eligible set every round (the
            # flagged set is bounded by the health registry's active set)
            from fedml_tpu.population import draw_uniform_distinct

            take = min(k, n - len(flagged))
            sel = draw_uniform_distinct(
                rng, n, take, exclude=np.asarray(flagged, np.int64)
            )
        else:
            eligible = np.setdiff1d(np.arange(n), np.asarray(flagged, np.int64))
            take = min(k, len(eligible))
            sel = rng.choice(eligible, size=take, replace=False) if take else np.empty(0, np.int64)
        if take < k:
            # top up with the least-bad stragglers: slowest last
            by_speed = sorted(
                flagged,
                key=lambda c: (ctx.health.mean_train_s(c) or 0.0, c),
            )
            sel = np.concatenate([sel, np.asarray(by_speed[: k - take], np.int64)])
        return np.sort(sel.astype(np.int64))


class OverprovisionPolicy(SelectionPolicy):
    """Wrap any policy and select ``ceil(k * factor)`` clients (clamped
    to the population) — the deadline/quorum companion: a quorum round
    that expects stragglers/dropouts still closes with ~k useful uploads.
    Registered as ``overprovision`` mostly for introspection; runtimes
    normally compose it via :func:`make_policy`'s factor argument."""

    name = "overprovision"

    def __init__(self, inner: SelectionPolicy, factor: float = 1.0):
        if factor < 1.0:
            raise ValueError("overprovision factor must be >= 1.0")
        self.inner = inner
        self.factor = float(factor)

    def select(self, round_idx: int, k: int, ctx: SelectionContext) -> np.ndarray:
        return self.inner.select(
            round_idx, overprovisioned_k(k, self.factor, ctx.num_clients), ctx
        )


_POLICIES["overprovision"] = lambda inner=None, factor=1.0: OverprovisionPolicy(
    inner or UniformPolicy(), factor
)

#: the policy names a config/CLI may name directly (overprovision is a
#: wrapper, composed via overprovision_factor, not selected by name)
POLICY_NAMES = ("uniform", "weighted", "power_of_choice", "straggler_aware")


def overprovisioned_k(k: int, factor: float, num_clients: int) -> int:
    """ceil(k * factor) clamped to the population — the ONE definition of
    the overprovisioned cohort size, shared by the policy wrapper and by
    the transport runner that must spawn one worker per selected client."""
    return max(1, min(int(num_clients), int(math.ceil(k * float(factor)))))


def make_policy(name: str, overprovision_factor: float = 1.0, **kw) -> SelectionPolicy:
    """Build a registered policy, wrapped in overprovisioning when
    ``overprovision_factor > 1``."""
    inner = get_policy(name, **kw)
    if overprovision_factor and overprovision_factor != 1.0:
        return OverprovisionPolicy(inner, overprovision_factor)
    return inner


def select_clients(
    round_idx: int,
    num_clients: int,
    k: int,
    policy: str = "uniform",
    seed: int = 0,
    sample_counts=None,
    losses=None,
    health=None,
) -> np.ndarray:
    """One-shot selection through the registry — the convenience entry for
    call sites with no scheduler object (fednas, the hierarchical bridge,
    and the back-compat ``fedavg.client_sampling`` shim)."""
    ctx = SelectionContext(
        seed=seed,
        num_clients=int(num_clients),
        sample_counts=sample_counts,
        losses=losses,
        health=health,
    )
    return get_policy(policy).select(int(round_idx), int(k), ctx)


class ClientScheduler:
    """The per-run selection driver every runtime shares: policy + context
    + per-round memo + the telemetry/metrics fan-out.

    - ``select(r)`` is memoized per round, so the round pipeline's
      look-ahead, the round loop, and a checkpoint writer all see ONE
      decision per round; the memo (plus the loss map feeding
      power_of_choice) is exactly the state ``state_dict`` persists so a
      resumed run re-selects its in-flight cohort byte-identically.
    - every fresh decision is emitted as a ``select`` telemetry span
      (policy/round/cohort attrs) and forwarded through ``on_select`` —
      the runtimes route that into MetricsLogger so summary.json records
      the selected-client set (the CI oracle contract).
    """

    def __init__(
        self,
        num_clients: int,
        k: int,
        policy: str = "uniform",
        seed: int = 0,
        overprovision_factor: float = 1.0,
        sample_counts: Optional[Sequence[int]] = None,
        health: Optional[object] = None,
        tracer: Optional[object] = None,
        on_select: Optional[Callable[[int, np.ndarray], None]] = None,
        memoize: bool = True,
        index: Optional[object] = None,
        ocohort_threshold: int = 65536,
        loss_map_capacity: int = 65536,
        selection_memo_rounds: int = 64,
    ):
        from fedml_tpu.population import BoundedLossMap

        self.num_clients = int(num_clients)
        self.k = int(k)
        self.policy_name = policy
        self.overprovision_factor = float(overprovision_factor)
        self._policy = make_policy(policy, overprovision_factor)
        self._ctx = SelectionContext(
            seed=int(seed),
            num_clients=self.num_clients,
            sample_counts=(
                np.asarray(sample_counts, np.int64)
                if sample_counts is not None
                else None
            ),
            # bounded: the power_of_choice bias map may never grow O(N)
            # (it is the "sched" checkpoint slot — an unbounded dict
            # over ever-seen clients at 1M clients IS the checkpoint);
            # a missing entry already means "cold client, explore"
            losses=BoundedLossMap(loss_map_capacity),
            health=health,
            index=index,
            ocohort_threshold=int(ocohort_threshold),
        )
        self._memo_rounds = int(selection_memo_rounds)
        self._tracer = tracer
        self._on_select = on_select
        self._memoize = bool(memoize)
        self._selections: Dict[int, np.ndarray] = {}

    @classmethod
    def from_config(
        cls, config, num_clients: int, data=None, log_fn=None, **kw
    ) -> "ClientScheduler":
        """Build from a RunConfig (FedConfig.selection /
        .overprovision_factor / .client_num_per_round + RunConfig.seed).

        ``data`` (a FederatedDataset) derives the weighted-policy sample
        counts — used only when its client count matches the federation's
        (a transport server may be configured against a larger population
        than the dataset it evaluates with). ``log_fn`` installs the
        standard on_select forwarding (the summary.json
        ``scheduler/policy``/``scheduler/selected`` row) — ONE definition
        of both, so the sim/transport/fedbuff runtimes cannot drift."""
        policy = getattr(config.fed, "selection", "uniform")
        pop_cfg = getattr(config, "population", None)
        if pop_cfg is not None:
            kw.setdefault("ocohort_threshold", pop_cfg.ocohort_threshold)
            kw.setdefault("loss_map_capacity", pop_cfg.loss_map_capacity)
            kw.setdefault(
                "selection_memo_rounds", pop_cfg.selection_memo_rounds
            )
        if "sample_counts" not in kw and data is not None and (
            data.num_clients == num_clients
        ):
            # vectorized property (np.diff over the mmap store's offsets;
            # one build-time pass for list-backed datasets) — never the
            # per-client Python len() loop at 1M clients
            kw["sample_counts"] = np.asarray(
                data.train_sample_counts, np.int64
            )
            if (
                "index" not in kw
                and num_clients >= kw.get("ocohort_threshold", 65536)
            ):
                # build the packed population index ONCE here (O(N),
                # build time) so every runtime sharing this config —
                # simulator, transports, fedbuff — engages the identical
                # O(cohort) draws (cohort-parity by construction)
                from fedml_tpu.population import PopulationIndex

                kw["index"] = PopulationIndex.from_counts(
                    kw["sample_counts"],
                    path=(pop_cfg.index_dir or None) if pop_cfg else None,
                    mmap_threshold_bytes=(
                        pop_cfg.index_mmap_bytes if pop_cfg else 64 << 20
                    ),
                )
        if "on_select" not in kw and log_fn is not None:
            kw["on_select"] = lambda r, sel: log_fn(
                {
                    "round": int(r),
                    "scheduler/policy": policy,
                    "scheduler/selected": [int(c) for c in sel],
                }
            )
        return cls(
            num_clients=num_clients,
            k=config.fed.client_num_per_round,
            policy=policy,
            seed=config.seed,
            overprovision_factor=getattr(config.fed, "overprovision_factor", 1.0),
            **kw,
        )

    def cohort_size(self) -> int:
        """Clients selected per round after overprovisioning — the worker
        count a transport runner must spawn."""
        return overprovisioned_k(
            self.k, self.overprovision_factor, self.num_clients
        )

    def select(self, round_idx: int, k: Optional[int] = None) -> np.ndarray:
        """This round's cohort. ``k`` overrides the configured size
        verbatim (no overprovision rescale — the transport server passes
        its already-provisioned worker count)."""
        r = int(round_idx)
        if self._memoize and r in self._selections:
            return self._selections[r]
        if k is None:
            sel = self._policy.select(r, self.k, self._ctx)
        else:
            # explicit k: bypass the overprovision wrapper (k is final)
            inner = getattr(self._policy, "inner", self._policy)
            sel = inner.select(r, int(k), self._ctx)
        sel = np.asarray(sel, np.int64)
        if self._memoize:
            self._selections[r] = sel
            # the LIVE memo is bounded too, not just the checkpointed
            # one: a continuous serve-layer session runs rounds
            # indefinitely, and an unbounded per-round dict is exactly
            # the growth class the population runtime removes. Evicted
            # rounds re-derive as pure functions of (seed, round) — the
            # same property state_dict's bound already relies on. The
            # floor keeps the round pipeline's look-ahead and the
            # short-run test contracts (full-run selections()) intact.
            cap = max(self._memo_rounds, 64)
            while len(self._selections) > cap:
                del self._selections[next(iter(self._selections))]
        if self._tracer is not None:
            timing = self._tracer.current_span()
            if timing is not None and timing.name == "select":
                # the caller times this selection with a span of its own
                # (FedAvgAPI._round_plan): name the policy there
                timing.set_attr("policy", self.policy_name)
            else:
                with self._tracer.span(
                    "select",
                    round=r,
                    policy=self.policy_name,
                    clients=int(len(sel)),
                ):
                    pass
        if self._on_select is not None:
            self._on_select(r, sel)
        return sel

    @property
    def wants_client_losses(self) -> bool:
        """True when the active policy biases on per-client losses
        (power_of_choice, possibly overprovision-wrapped) — the signal
        the vmap round program's ``client_loss_sum``/``client_count``
        vectors exist to feed (FedAvgAPI._report_client_losses)."""
        inner = getattr(self._policy, "inner", self._policy)
        return isinstance(inner, PowerOfChoicePolicy)

    def report_loss(self, client_id: int, loss: float) -> None:
        """Feed a client's last observed local train loss
        (power_of_choice's bias signal). Any runtime may call this with
        whatever loss signal it has — true per-client loss on the
        transports, the cohort mean in the vmap simulator. The vmap
        simulator upgrades to TRUE per-client losses when
        :attr:`wants_client_losses` (sim/transport parity for
        power_of_choice)."""
        if loss is None or not np.isfinite(loss):
            return
        self._ctx.losses[int(client_id)] = float(loss)

    def selections(self) -> Dict[int, List[int]]:
        """Memoized decisions so far, JSON-ready ({round: [ids]}) — the
        most recent ``max(selection_memo_rounds, 64)`` rounds (the live
        memo is bounded; see :meth:`select`)."""
        return {r: [int(c) for c in sel] for r, sel in sorted(self._selections.items())}

    # -- checkpoint support (utils/checkpoint.py "sched" slot) --
    def state_dict(self) -> dict:
        """Pytree of numpy arrays (checkpoint-flattenable): the per-round
        selection memo + the loss map. Enough to re-select the in-flight
        round byte-identically after a resume — policies are otherwise
        pure functions of (seed, round).

        BOUNDED by construction (population-scale checkpoint contract,
        pinned by tests/test_population.py): the loss map is a
        BoundedLossMap (at most ``loss_map_capacity`` entries, never
        O(N) at 1M clients), and only the most recent
        ``selection_memo_rounds`` rounds' selections persist — a resume
        only ever re-derives its in-flight round, and every policy is a
        pure function of (seed, round) beyond that."""
        rounds = sorted(self._selections)[-self._memo_rounds:]
        loss_ids = sorted(self._ctx.losses)
        return {
            "rounds": np.asarray(rounds, np.int64),
            "selections": [
                np.asarray(self._selections[r], np.int64) for r in rounds
            ],
            "loss_ids": np.asarray(loss_ids, np.int64),
            "loss_vals": np.asarray(
                [self._ctx.losses.get(i) for i in loss_ids], np.float64
            ),
        }

    def load_state_dict(self, state: dict) -> None:
        from fedml_tpu.population import BoundedLossMap

        rounds = [int(r) for r in np.asarray(state["rounds"]).ravel()]
        self._selections = {
            r: np.asarray(sel, np.int64)
            for r, sel in zip(rounds, state["selections"])
        }
        ids = np.asarray(state["loss_ids"]).ravel()
        vals = np.asarray(state["loss_vals"]).ravel()
        losses = BoundedLossMap(self._ctx.losses.capacity)
        for i, v in zip(ids, vals):
            losses[int(i)] = float(v)
        self._ctx.losses = losses
