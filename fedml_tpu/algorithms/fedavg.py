"""FedAvg — the flagship algorithm (ref: fedml_api/distributed/fedavg/ +
fedml_api/standalone/fedavg/).

The reference spends ~566 LoC on a server FSM + client managers + MPI wire
(SURVEY §3.1); here the whole communication round is one pure function::

    (global_variables, stacked_client_batch, weights, rng)
        -> (global_variables', metrics)

vmap over the client axis = the standalone simulator
(ref fedavg_api.py:40-84's sequential loop, HOT LOOP of SURVEY §3.2);
the same function jitted with the client axis sharded over a device mesh =
the distributed runtime (ref FedAvgServerManager/ClientManager + MPI).
Aggregation is the sample-weighted average of FedAVGAggregator.py:51-78 as a
single tensordot over the client axis (XLA lowers it to an all-reduce when
sharded) instead of a Python loop over state_dict keys (HOT LOOP #3)."""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.analysis.sentinel import ensure_backend_listener
from fedml_tpu.config import RunConfig
from fedml_tpu.data.base import FederatedDataset, stack_clients
from fedml_tpu.models import ModelDef
from fedml_tpu.telemetry import ClientHealthRegistry, get_tracer
from fedml_tpu.train.client import make_local_train
from fedml_tpu.train.evaluate import make_eval_fn
from fedml_tpu.utils.profiling import span_annotation


def weighted_average(stacked_tree, weights):
    """Sample-weighted average over the leading client axis
    (ref FedAVGAggregator.py:51-78: w = n_k/n_total per key)."""
    wsum = jnp.sum(weights)
    return jax.tree_util.tree_map(
        lambda p: jnp.tensordot(weights, p.astype(jnp.float32), axes=1) / wsum,
        stacked_tree,
    )


def client_sampling(round_idx: int, client_num_in_total: int, client_num_per_round: int) -> np.ndarray:
    """Round-seeded sampling for reproducibility — exact parity with
    FedAVGAggregator.py:80-88 (np.random.seed(round_idx) then choice without
    replacement). Back-compat shim: the implementation now lives in the
    scheduler registry as the ``uniform`` policy
    (fedml_tpu/scheduler/policies.py); this delegates so every historical
    import keeps the exact reference semantics."""
    from fedml_tpu.scheduler import select_clients

    return select_clients(
        round_idx, client_num_in_total, client_num_per_round, policy="uniform"
    )


def round_client_rngs(round_rng, num_sampled: int):
    """Per-client PRNG keys for one round. Generated once per round from the
    round-folded key so the stream is independent of how clients are later
    padded/sharded over a mesh (single-chip and N-shard runs see identical
    per-client randomness)."""
    return jax.random.split(round_rng, num_sampled)


def resolve_client_parallelism(mode: str, model: ModelDef) -> str:
    """Resolve FedConfig.client_parallelism="auto" for a model.

    "scan" wins when per-client weights make vmap's convs grouped convs
    whose small channel dims tile the 128-lane MXU badly (measured on v5e:
    cross-silo ResNet-56 bf16 round 350 -> 190 ms under scan; the flagship
    femnist CNN is a wash, 34.0 -> 33.1 ms, because its dense head runs at
    the same tiny per-client M either way). Models without under-tiled convs
    or with sub-MB param copies keep "vmap": their per-step time is
    overhead-dominated and one big program wins. The heuristic: any 4-D
    conv kernel with <= 64 output channels (under-tiled on the MXU) and a
    per-client param copy >= 1 MB.

    "scan" also wins by bytes: under vmap every client of the cohort holds
    its float32 copy, that copy's gradient and the compute-dtype copy at
    once (10 bytes a parameter at bf16), under scan one client does. A
    per-client copy of 1 GiB or more (268 M float32 parameters) makes that
    2.5 GiB a client: two clients of such a model leave a 16 GB chip too
    little for their activations, and its matmuls are large enough that
    batching them over clients buys nothing. (GPT-2 124M with its untied
    head is 0.61 GiB and keeps vmap.)"""
    if mode == "auto":
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        leaves = jax.tree_util.tree_leaves(shapes)
        param_bytes = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize for l in leaves
        )
        small_conv = any(
            len(l.shape) == 4 and l.shape[-1] <= 64 and l.shape[0] <= 7
            for l in leaves
        )
        under_tiled = small_conv and param_bytes >= 1_000_000
        mode = "scan" if under_tiled or param_bytes >= 2**30 else "vmap"
    if mode not in ("vmap", "scan"):
        raise ValueError(
            f"client_parallelism must be 'vmap', 'scan' or 'auto', got {mode!r}"
        )
    return mode


def client_axis_map(local_train: Callable, mode: str, n_broadcast: int = 1) -> Callable:
    """Lift ``local_train`` over the leading client axis — either batched
    (vmap) or sequential (lax.scan). The first ``n_broadcast`` positional
    args broadcast to every client (global state: variables, and e.g.
    SCAFFOLD's server control variate); the rest carry a leading client
    axis. Both schedules return identically stacked outputs; the math is
    the same, only the schedule differs (see resolve_client_parallelism)."""
    if mode == "vmap":

        def vmapped(*args):
            in_axes = (None,) * n_broadcast + (0,) * (len(args) - n_broadcast)
            return jax.vmap(local_train, in_axes=in_axes)(*args)

        return vmapped

    def scanned(*args):
        bcast, per = args[:n_broadcast], args[n_broadcast:]

        def body(_, per_client):
            return None, local_train(*bcast, *per_client)

        _, out = jax.lax.scan(body, None, per)
        return out

    return scanned


def resolve_skip_empty_steps(mode: str, may_pad: Optional[bool]) -> bool:
    """Whether the per-step ``lax.cond`` skip branch should be emitted.

    The cond genuinely skips all-padding steps under the sequential
    ("scan") client schedule — but it is not free: interleaved-min on the
    cross-silo ResNet-56 round, the cond-ful body costs ~3% (188.0 vs
    182.5 ms) when every step is real, presumably because the branch
    boundary blocks XLA from fusing the batch slice into the step. Whether
    a cohort HAS any all-padding step is host-side static knowledge (the
    sampled clients' sample counts vs the bucketed step count), so the
    decision is made per compiled shape class: ``may_pad=False`` drops the
    cond entirely, ``may_pad=True`` keeps it, ``None`` (unknown cohort)
    keeps the safe default under scan. vmap schedules never emit it — a
    per-client predicate cannot branch."""
    if mode != "scan":
        return False
    return True if may_pad is None else bool(may_pad)


def make_fedavg_round_body(
    model: ModelDef,
    config: RunConfig,
    task: str = "classification",
    local_train_fn: Optional[Callable] = None,
    client_mode: Optional[str] = None,
    may_pad: Optional[bool] = None,
):
    """The unjitted plain-FedAvg round body: lifted local trains + weighted
    average. ``(global_vars, x, y, mask, num_samples, client_rngs) ->
    (global_vars', per_client_metrics)``. Shared by the jitted round fn and
    by device-time measurement (utils/profiling.scan_slope_seconds needs an
    unjitted body to repeat inside one program)."""
    mode = client_mode or resolve_client_parallelism(
        config.fed.client_parallelism, model
    )
    local_train = local_train_fn or make_local_train(
        model, config.train, config.fed.epochs, task=task,
        skip_empty_steps=resolve_skip_empty_steps(mode, may_pad),
    )
    lifted = client_axis_map(local_train, mode)

    def round_body(global_vars, x, y, mask, num_samples, client_rngs):
        client_vars, metrics = lifted(global_vars, x, y, mask, client_rngs)
        return weighted_average(client_vars, num_samples), (client_vars, metrics)

    return round_body


def make_fedavg_round(
    model: ModelDef,
    config: RunConfig,
    task: str = "classification",
    local_train_fn: Optional[Callable] = None,
    donate: bool = True,
    post_train: Optional[Callable] = None,
    post_aggregate: Optional[Callable] = None,
    aggregate_fn: Optional[Callable] = None,
    client_mode: Optional[str] = None,
    client_metrics: bool = False,
    robust=None,
):
    """Build the jitted FedAvg round function (vmap over clients, one chip).

    ``local_train_fn`` lets algorithm variants (FedProx via prox_mu, FedNova
    via its own trainer) reuse this round skeleton. ``post_train(client_vars,
    global_vars, *extra)`` transforms the stacked per-client results before
    averaging (robust clipping); ``post_aggregate(new_global, *extra)``
    transforms the average (weak-DP noise); any positional round-fn
    arguments beyond client_rngs are forwarded to both hooks (e.g. a noise
    rng supplied by the API's _place_batch).

    ``client_metrics=True`` additionally returns per-client
    ``client_loss_sum``/``client_count`` vectors (leading client axis)
    alongside the scalar sums — the true per-client loss signal
    ``power_of_choice`` selection biases on (cohort-mean feeding made the
    simulator's bias signal diverge from the transports', ROADMAP item).
    Off by default: callers that combine metric trees across cohorts of
    different sizes (the hierarchical group loop) must not see
    ragged-shaped leaves.

    ``robust`` (a :class:`fedml_tpu.robustness.RobustConfig`) is the
    DESCRIBABLE form of the defense hook triple: the hooks are derived
    inside the builder from the config alone
    (``make_defense_hooks(robust)`` is a pure function of it), so the
    robust round — including the Byzantine aggregators
    median/trimmed-mean/Krum — dedupes through the ProgramCache with
    ``robust`` in the digest instead of bypassing via ``wrap_uncached``
    the way opaque hook closures must. Mutually exclusive with passing
    the hook closures directly.

    The returned callable takes an optional keyword ``may_pad`` — the
    host's static knowledge of whether this cohort has any all-padding
    local step (see :func:`resolve_skip_empty_steps`). Each distinct
    answer compiles its own variant (lazily, at most two); an unknown
    cohort (``None``) gets the safe default."""
    mode = client_mode or resolve_client_parallelism(
        config.fed.client_parallelism, model
    )
    # Program dedup (fedml_tpu/compile/): the jit cache is keyed by the
    # jit OBJECT, so every factory call would otherwise compile its own
    # copy of a structurally identical round. When the program is fully
    # determined by describable fields (no opaque hooks), route through
    # the process-wide ProgramCache; opaque callables bypass it — an
    # over-merged digest would be silent wrong numerics.
    from fedml_tpu.compile import (
        get_program_cache,
        hooks_cacheable,
        model_fingerprint,
    )

    if robust is not None:
        if not hooks_cacheable(post_train, post_aggregate, aggregate_fn):
            raise ValueError(
                "pass either robust= (describable defense config) or "
                "explicit hook closures, not both"
            )
        from fedml_tpu.algorithms.fedavg_robust import make_defense_hooks

        post_train, post_aggregate, aggregate_fn = make_defense_hooks(robust)
        # the hooks are pure functions of the (digested) RobustConfig —
        # only a caller-supplied local train keeps the program opaque
        cacheable = hooks_cacheable(local_train_fn)
    else:
        cacheable = hooks_cacheable(
            local_train_fn, post_train, post_aggregate, aggregate_fn
        )

    def build(skip: bool):
        def builder():
            local_train = local_train_fn or make_local_train(
                model, config.train, config.fed.epochs, task=task,
                skip_empty_steps=skip,
            )
            lifted = client_axis_map(local_train, mode)

            def round_fn(global_vars, x, y, mask, num_samples, client_rngs, *extra):
                # named scopes put the layer into every device op's
                # ``op_name`` (metadata only: the arithmetic is untouched)
                with jax.named_scope("local_train"):
                    client_vars, metrics = lifted(global_vars, x, y, mask, client_rngs)
                if post_train is not None:
                    client_vars = post_train(client_vars, global_vars, *extra)
                # aggregate_fn replaces the weighted average outright (Byzantine-
                # robust aggregators: median/trimmed-mean/Krum; DP's fixed-
                # denominator estimator needs w_t, hence the third argument)
                with jax.named_scope("aggregate"):
                    if aggregate_fn is not None:
                        new_global = aggregate_fn(client_vars, num_samples, global_vars)
                    else:
                        new_global = weighted_average(client_vars, num_samples)
                if post_aggregate is not None:
                    new_global = post_aggregate(new_global, *extra)
                with jax.named_scope("round_metrics"):
                    agg_metrics = jax.tree_util.tree_map(jnp.sum, metrics)
                if (
                    client_metrics
                    and isinstance(metrics, dict)
                    and "loss_sum" in metrics
                    and "count" in metrics
                ):
                    # per-client loss signal for power_of_choice — the
                    # stacked (pre-sum) vectors ride along with the sums
                    agg_metrics["client_loss_sum"] = metrics["loss_sum"]
                    agg_metrics["client_count"] = metrics["count"]
                return new_global, agg_metrics

            return jax.jit(round_fn, donate_argnums=(0,) if donate else ())

        cache = get_program_cache()
        if not cacheable:
            return cache.wrap_uncached("fedavg_round", builder())
        return cache.get_or_build(
            "fedavg_round",
            {
                "kind": "fedavg_round",
                "model": model_fingerprint(model),
                "train": config.train,
                "epochs": config.fed.epochs,
                "task": task,
                "mode": mode,
                "skip": skip,
                "donate": donate,
                "client_metrics": client_metrics,
                # the whole RobustConfig dataclass (or None) enters the
                # digest — every leaf (defense_type, norm_bound, stddev,
                # num_byzantine/trim_k, multi_krum_m) shapes the traced
                # defense, and the digest audit's drop-field fuzz pins
                # that removing this key fails on exactly those leaves
                # (the scaffold eta_g hazard class)
                "robust": robust,
            },
            builder,
        )

    # A caller-supplied local_train_fn fixed its own skip choice at build
    # time — only the default local train can vary per cohort.
    can_vary = local_train_fn is None and mode == "scan"
    variants: dict = {}

    def variant_for(may_pad: Optional[bool] = None):
        """The underlying jitted round fn for a cohort — for callers that
        need the jit object itself (lower()/cost analysis)."""
        skip = resolve_skip_empty_steps(mode, may_pad if can_vary else None)
        fn = variants.get(skip)
        if fn is None:
            fn = variants[skip] = build(skip)
        return fn

    def dispatch(global_vars, *args, may_pad: Optional[bool] = None):
        return variant_for(may_pad)(global_vars, *args)

    dispatch.supports_may_pad = can_vary
    dispatch.variant_for = variant_for
    dispatch._variants = variants  # introspection for tests
    return dispatch


class FedAvgAPI:
    """Standalone FedAvg simulator (ref standalone/fedavg/fedavg_api.py:13-180).

    The reference reuses ``client_num_per_round`` Client objects and re-points
    them at sampled shards each round (fedavg_api.py:47-51); here the analogous
    move is restacking the sampled shards into one padded device batch.
    """

    # Subclasses that read the pre-round global model after the round call
    # (e.g. FedOpt's pseudo-gradient) must disable buffer donation.
    _donate = True
    # Subclasses with their own batch placement (the sharded API pads +
    # shards host arrays over the mesh) disable the HBM-resident store.
    _use_device_store = True
    # Whether this API's round fn may return per-client loss vectors
    # (power_of_choice's true bias signal). Subclasses that combine metric
    # trees across cohorts of different sizes (hierarchical groups) or
    # whose round programs don't emit the vectors (mesh shard_map) fall
    # back to the cohort-mean signal.
    _client_loss_vectors = True
    # Round pipeline (FedConfig.pipeline): subclasses whose train_round
    # bypasses the _round_placed stash contract (hierarchical group
    # loops) or whose _place_batch is not a pure function of
    # (round, config.seed, rng) (the backdoor attack mask reads
    # _current_round) set this False — preparing round r+1 during round
    # r would leak stashes or bake stale state into the batch.
    _supports_pipeline = True

    def __init__(
        self,
        config: RunConfig,
        data: FederatedDataset,
        model: ModelDef,
        task: str = "classification",
        local_train_fn: Optional[Callable] = None,
        log_fn: Optional[Callable[[dict], None]] = None,
    ):
        self._tracer = get_tracer()
        # every span is mirrored into a running jax profile as a
        # ``fedml.<name>`` annotation, on the profiler's clock
        self._tracer.annotate = span_annotation
        # and every program that jax traces, lowers and compiles or loads
        # from here on is a ``jit_*`` span under the span that paid for it
        ensure_backend_listener()
        # set-up, outside any round: parent of ``store_upload`` and of
        # whatever ``model.init`` and the store make jax compile
        with self._tracer.span("api_init") as sp:
            self._init_body(config, data, model, task, local_train_fn, log_fn)
            leaves = jax.tree_util.tree_leaves(self.global_vars)
            sp.set_attr("params", sum(int(x.size) for x in leaves))
            sp.set_attr("param_bytes", sum(int(x.nbytes) for x in leaves))
            sp.set_attr("client_mode", self._client_mode)

    def _init_body(self, config, data, model, task, local_train_fn, log_fn):
        self.config = config
        self.data = data
        self.model = model
        self.task = task
        self.log_fn = log_fn or (lambda m: None)
        self.rng = jax.random.PRNGKey(config.seed)
        self.global_vars = model.init(jax.random.fold_in(self.rng, 0))
        self._local_train_fn = local_train_fn
        self._round_plans: dict = {}  # round_idx -> (sampled, steps, bs)
        self._may_pad_cache: dict = {}  # round_idx -> bool
        self._client_mode = resolve_client_parallelism(
            config.fed.client_parallelism, model
        )
        self.round_fn = self._build_round_fn(local_train_fn)
        self.eval_fn = make_eval_fn(model, task)
        self.history: list = []
        # Resume support: CLI/--resume sets global_vars + start_round from a
        # checkpoint; train() continues the round loop from there (the
        # round-seeded sampling makes the continuation identical to the
        # uninterrupted run).
        self.start_round = 0
        # Telemetry: round-lifecycle spans (round → broadcast/local_train/
        # eval) and the loop's own host phases (pack, prepare, health,
        # flush: docs/OBSERVABILITY.md) on the global tracer, and a client
        # health registry updated per round. The vmap/mesh runtimes run the whole cohort as ONE
        # jitted program, so per-client "train time" here is the cohort's
        # shared round wall time — participation/last-seen stay exact, and
        # the transport runtimes refine timing per client.
        self.health = ClientHealthRegistry.from_config(config)
        # the model's host constants that every ``flush`` span carries, at
        # the samples of a local step (ModelDef.flush_attrs)
        self._flush_attrs = model.flush_attrs(self._step_batch())
        # Scheduler: policy-driven cohort selection (FedConfig.selection /
        # .overprovision_factor, scheduler/policies.py). It shares this
        # API's health registry (straggler_aware consults the straggler
        # flags) and forwards every fresh decision into log_fn, so
        # summary.json records the selected cohort (the CI oracle).
        from fedml_tpu.scheduler import ClientScheduler, FaultInjector

        self.scheduler = ClientScheduler.from_config(
            config,
            num_clients=data.num_clients,
            data=data,
            log_fn=self.log_fn,
            health=self.health,
            tracer=self._tracer,
        )
        # Fault injection (FedConfig.fault_plan): the vmap cohort trains
        # as ONE jitted program, so only participation faults apply here —
        # dropout/crash remove the client from the cohort at selection
        # time (see _apply_participation_faults); timing faults are
        # transport-only.
        self.faults = FaultInjector.from_config(
            config, health=self.health, tracer=self._tracer
        )
        self._fault_cache: dict = {}  # round -> post-fault survivors
        # rounds whose TRUE per-client losses were already fed to the
        # scheduler (train_round's vector fetch) — _log_round must not
        # overwrite them with the cohort mean
        self._client_loss_rounds: set = set()
        # round -> placed device batch, populated by the AOT warmup path
        # AND by the round pipeline (_pipeline_prepare) and consumed
        # (popped) by train_round so neither pays the round's stack + H2D
        # cost twice. The stash is the pipeline's COMMIT POINT: values are
        # pure in (round, config.seed, self.rng), so a stashed batch is
        # byte-identical to the one the serial schedule would build at the
        # round boundary.
        self._warm_placed: dict = {}
        # Round pipeline (FedConfig.pipeline): after round r's async
        # dispatch, the host prepares round r+1's cohort/batch/placement
        # while the device still executes r. _pipeline_overlap holds the
        # measured host seconds hidden per prepared round (attached to
        # that round's span as overlap_s → flight records);
        # pipeline_rounds counts rounds the pipeline prepared ahead.
        if config.fed.pipeline not in ("off", "auto", "on"):
            raise ValueError(
                "FedConfig.pipeline must be 'off', 'auto' or 'on'; got "
                f"{config.fed.pipeline!r}"
            )
        self._pipeline_overlap: dict = {}
        self.pipeline_rounds = 0
        self._store = None
        if self._use_device_store and config.data.device_cache:
            from fedml_tpu.data.device_store import DeviceDataStore, fits_on_device

            if fits_on_device(data):
                try:
                    # set-up, outside any round: what the device now holds
                    with self._tracer.span("store_upload") as sp:
                        store = DeviceDataStore(data)
                        sp.set_attr("rows", int(store.flat_x.shape[0]))
                        sp.set_attr("row_bytes", store.row_bytes)
                        sp.set_attr("resident_bytes", store.resident_bytes)
                    self._store = store
                except ValueError:
                    # ragged per-client feature shapes cannot concatenate —
                    # the one EXPECTED reason to fall back to host stacking
                    self._store = None
                except Exception:
                    # anything else is a real DeviceDataStore bug: falling
                    # back silently would hide a large perf regression
                    # behind identical results
                    import logging

                    logging.exception(
                        "DeviceDataStore init failed unexpectedly — "
                        "falling back to host stacking (SLOW path); "
                        "investigate, this is not the ragged-shape case"
                    )
                    self._store = None
        self._test_dev = None
        self._local_eval_dev = None  # local_test_on_all_clients cache

    def _build_round_fn(self, local_train_fn):
        return make_fedavg_round(
            self.model,
            self.config,
            task=self.task,
            local_train_fn=local_train_fn,
            donate=self._donate,
            client_mode=self._client_mode,
            client_metrics=self._wants_client_losses(),
        )

    def _wants_client_losses(self) -> bool:
        """True when the round program should emit per-client loss
        vectors: the selection policy feeds on per-client losses AND this
        API's round family supports the vectors. Derived from config (not
        the scheduler object — the round fn is built before it)."""
        return (
            self._client_loss_vectors
            and self.config.fed.selection == "power_of_choice"
        )

    def warmup(self, log_fn=None):
        """AOT-compile this run's programs before round 0
        (``jit(...).lower(...).compile()`` — fedml_tpu/compile/warmup.py):
        the round program for ``start_round``'s cohort shapes, EVERY other
        (steps, bs) shape class the partition can produce (derived via
        ``bucket_steps`` over all client sizes — rounds 1..R never hit a
        lazy shape-bucket compile; classes past the warmup cap still
        compile lazily, compile/warmup.py), the eval program, and the
        server-optimizer step when present. When a persistent executable
        cache is installed, warmed programs load from / export to disk,
        so a fresh process warms with zero backend compiles. Emits
        ``compile`` telemetry spans and forwards per-program compile
        seconds + XLA cost analysis (flops/bytes) through ``log_fn`` into
        summary.json. Executes nothing — warm runs are numerically
        identical to cold runs, and warm-from-disk runs byte-identical to
        warm-in-process runs (tests/test_compile.py)."""
        from fedml_tpu.compile import warmup_api

        return warmup_api(self, log_fn=log_fn or self.log_fn)

    def train_round(self, round_idx: int):
        # _round_plan is the one derivation of "this round's cohort" —
        # memoized, shared with the pipeline's prepare and _round_may_pad
        sampled, _steps, _bs = self._round_plan(round_idx)
        # "broadcast" = ship the global model + cohort batch to the device
        # (the simulator's analog of the transport path's model broadcast)
        with self._tracer.span(
            "broadcast", round=round_idx, clients=len(sampled),
            prepared=round_idx in self._warm_placed,
        ):
            # the AOT warmup path (or the round pipeline, which prepared
            # this round while the previous one executed) already stacked
            # + placed this round's batch — consume it instead of paying
            # the host stack + H2D transfer twice (the inputs are pure
            # functions of (round, rng), so the values are identical
            # either way)
            placed = self._round_placed(round_idx, sampled)
        kw = {}
        if getattr(self.round_fn, "supports_may_pad", False):
            kw["may_pad"] = self._round_may_pad(round_idx)
        # local train + weighted aggregate run fused in ONE jitted program;
        # dispatch is async, so this span's wall time is the host-side
        # dispatch cost, not device time (the device half lives in the
        # --profile_dir jax trace)
        with self._tracer.span(
            "local_train", round=round_idx, clients=len(sampled), fused_aggregate=True
        ):
            self.global_vars, metrics = self.round_fn(
                self.global_vars, *placed, **kw
            )
        if (
            isinstance(metrics, dict)
            and "client_loss_sum" in metrics
            and self.scheduler.wants_client_losses
        ):
            self._report_client_losses(sampled, metrics, round_idx)
        return sampled, metrics

    def _round_placed(self, round_idx: int, sampled):
        """This round's placed device batch: the warmup/pipeline stash
        when one exists (byte-identical by the determinism contract —
        every input is pure in (round, config.seed, self.rng), and
        self.rng is never reassigned after __init__), else built now.
        Shared by FedAvg's train_round and the stateful subclasses
        (SCAFFOLD/Ditto), so the pipeline serves all of them."""
        placed = self._warm_placed.pop(round_idx, None)
        if placed is not None:
            return placed
        return self._build_placed(round_idx, sampled)

    def _build_placed(self, round_idx: int, sampled):
        """Stack round ``round_idx``'s cohort batch and place it on the
        device: the one place a round's batch is built, for the round
        itself (``_round_placed``) or a round early (``_pipeline_prepare``).
        ``stack`` is the host's index building plus the gather's dispatch;
        ``place`` carries the batch's counts (shapes and host numbers, no
        device read)."""
        with self._tracer.span("stack", round=round_idx) as sp:
            batch = self._round_batch(sampled, round_idx)
            sp.set_attr("steps", int(batch.mask.shape[1]))
            sp.set_attr("bs", int(batch.mask.shape[2]))
        rng = jax.random.fold_in(self.rng, round_idx + 1)
        with self._tracer.span(
            "place", round=round_idx,
            slots=math.prod(batch.mask.shape),
            real_samples=float(np.sum(batch.num_samples)),
        ):
            return self._place_batch(batch, rng)

    def _pipeline_prepare(self, next_round: int) -> None:
        """The round pipeline's host stage: while the JUST-DISPATCHED
        round still executes on device (async dispatch), select round
        ``next_round``'s cohort, gather/stack its batch, and issue its
        H2D placement, stashing the result under the ``_warm_placed``
        commit contract. Degrades to serial (returns without stashing)
        whenever preparing ahead could change what the serial schedule
        would do:

        - pipeline "off", or a class that opts out (``_supports_pipeline``);
        - adaptive selection (power_of_choice / straggler_aware feed on
          round r's losses/straggler flags before selecting r+1);
        - an active fault plan with participation faults (cohorts shrink
          per round; fault accounting must describe executed rounds).

        The ``prepare`` span's seconds land in ``_pipeline_overlap`` and
        ride the next round's span as ``overlap_s`` (flight records)."""
        cfg = self.config
        if (
            cfg.fed.pipeline == "off"
            or not self._supports_pipeline
            or next_round >= cfg.fed.comm_round
        ):
            return
        if next_round in self._warm_placed:
            return  # warmup already stashed it
        if cfg.fed.selection in ("power_of_choice", "straggler_aware"):
            return
        if (
            self.faults is not None
            and self.faults.plan.has_participation_faults()
        ):
            return
        with self._tracer.span("prepare", round=next_round) as sp:
            sampled, _steps, _bs = self._round_plan(next_round)
            self._warm_placed[next_round] = self._build_placed(
                next_round, sampled
            )
        self._pipeline_overlap[next_round] = sp.dur_us / 1e6
        self.pipeline_rounds += 1

    def _report_client_losses(self, sampled, metrics, round_idx: int):
        """Feed the scheduler TRUE per-client losses from the round's
        ``client_loss_sum``/``client_count`` vectors — the same per-client
        mean the transport clients attach to their uploads
        (ARG_TRAIN_LOSS), so sim and transport power_of_choice bias on
        identical signals and select identical cohorts. The fetch blocks
        on the round (adaptive policies run serial, per-round: the
        pipeline prepares nothing ahead for them)."""
        losses = np.asarray(metrics["client_loss_sum"])[: len(sampled)]
        counts = np.asarray(metrics["client_count"])[: len(sampled)]
        for cid, s, c in zip(sampled, losses, counts):
            if c > 0:
                self.scheduler.report_loss(int(cid), float(s) / float(c))
        self._client_loss_rounds.add(int(round_idx))

    def _step_batch(self) -> int:
        """Samples a local step trains on: the batch size, or in full-batch
        mode the largest client's count."""
        bs = self.config.data.batch_size
        return bs if bs != -1 else int(max(len(y) for y in self.data.client_y))

    def _client_counts(self, sampled):
        if self._store is not None:
            return [int(self._store.counts[i]) for i in sampled]
        return [len(self.data.client_y[i]) for i in sampled]

    def _round_may_pad(self, round_idx: int) -> bool:
        """Memoized per-round _cohort_may_pad — the round, warm-up and
        ``round_program`` all ask, and the count loop + bucket math is
        host time in the loop."""
        v = self._may_pad_cache.get(round_idx)
        if v is None:
            v = self._may_pad_cache[round_idx] = self._cohort_may_pad(
                self._round_plan(round_idx)[0]
            )
        return v

    def _cohort_may_pad(self, sampled) -> bool:
        """True iff some sampled client has at least one ALL-padding local
        step — i.e. fewer full batches than the cohort's bucketed step
        count. Host-side static knowledge: picks the round variant with or
        without the per-step cond skip (see resolve_skip_empty_steps)."""
        from fedml_tpu.data.base import bucket_steps

        cfg = self.config
        counts = self._client_counts(sampled)
        steps, bs, _ = bucket_steps(
            counts, cfg.data.batch_size, cfg.data.pad_bucket
        )
        return any(-(-int(n) // bs) < steps for n in counts)

    def _stack(self, client_indices, seed: int):
        """Clients as a dense batch: device-store gather (only an index
        matrix crosses the wire) or host stacking fallback. Both paths use
        the same seed/bucket contract, so the math is identical."""
        cfg = self.config
        if self._store is not None:
            return self._store.round_batch(
                client_indices,
                cfg.data.batch_size,
                seed=seed,
                pad_bucket=cfg.data.pad_bucket,
            )
        return stack_clients(
            self.data,
            client_indices,
            cfg.data.batch_size,
            seed=seed,
            pad_bucket=cfg.data.pad_bucket,
        )

    def _round_batch(self, sampled, round_idx: int):
        return self._stack(sampled, self.config.seed * 1_000_003 + round_idx)

    def local_test_on_all_clients(self, round_idx: int = 0) -> Dict[str, float]:
        """Evaluate the global model on every client's local data (ref
        fedavg_api.py:117-180 ``_local_test_on_all_clients``): train metrics
        over all clients' train shards, test metrics over their test shards
        (falling back to the central test set when the dataset has no
        per-client test split). The reference aggregates per-client sums
        with sample weights — identical to pooled evaluation, so the shards
        are concatenated and run through the jitted eval fn in one pass.
        ``fed.ci`` short-circuits to client 0 only (ref :162-167)."""
        from fedml_tpu.train.evaluate import pad_to_batches

        if self._local_eval_dev is None:
            # the pooled shards are round-invariant: pad + place on device
            # ONCE (same reason evaluate_global caches _test_dev)
            ci = self.config.fed.ci
            ids = [0] if ci else range(self.data.num_clients)
            xs = np.concatenate([self.data.client_x[i] for i in ids], axis=0)
            ys = np.concatenate([self.data.client_y[i] for i in ids], axis=0)
            self._local_eval_dev = {
                split: tuple(
                    map(jnp.asarray, pad_to_batches(x, y, 256))
                )
                for split, (x, y) in {
                    "Train": (xs, ys),
                    "Test": self._client_test_pool(ids),
                }.items()
            }
        from fedml_tpu.train.evaluate import metrics_to_loss_acc

        row = {"round": round_idx}
        for split, batches in self._local_eval_dev.items():
            loss, acc = metrics_to_loss_acc(
                self.eval_fn(self.global_vars, *batches)
            )
            row[f"{split}/Loss"], row[f"{split}/Acc"] = loss, acc
        return row

    def _client_test_pool(self, ids):
        if self.data.client_test_x is not None:
            return (
                np.concatenate([self.data.client_test_x[i] for i in ids], axis=0),
                np.concatenate([self.data.client_test_y[i] for i in ids], axis=0),
            )
        return np.asarray(self.data.test_x), np.asarray(self.data.test_y)

    def _eval_batches(self):
        """The central test set as padded device batches, cached (the host
        arrays would otherwise be re-shipped every eval). Shared by
        evaluate_global and the AOT warmup path, so the warmed eval
        program sees exactly the shapes the run will dispatch."""
        from fedml_tpu.train.evaluate import pad_to_batches

        if self._test_dev is None:
            xb, yb, mb = pad_to_batches(
                np.asarray(self.data.test_x), np.asarray(self.data.test_y), 256
            )
            self._test_dev = (jnp.asarray(xb), jnp.asarray(yb), jnp.asarray(mb))
        return self._test_dev

    def evaluate_global(self):
        """(loss, acc) of the global model on the central test set."""
        from fedml_tpu.train.evaluate import metrics_to_loss_acc

        return metrics_to_loss_acc(
            self.eval_fn(self.global_vars, *self._eval_batches())
        )

    def round_program(self, round_idx: int = 0):
        """``(program, args)`` that ``train_round(round_idx)`` dispatches,
        for lowering (cost analysis, AOT compiles, reading the optimized
        HLO). Building it executes nothing."""
        sampled, _steps, _bs = self._round_plan(round_idx)
        batch = self._round_batch(sampled, round_idx)
        rng = jax.random.fold_in(self.rng, round_idx + 1)
        fn = self.round_fn
        if hasattr(fn, "variant_for"):
            fn = fn.variant_for(self._round_may_pad(round_idx))
        return fn, (self.global_vars, *self._place_batch(batch, rng))

    def _spill_pad_ids(self, sampled):
        """(store-gather ids, real count) for the stateful algorithms'
        SPILLED state tier. Defined on the common root so the mesh
        runtime's override (DistributedFedAvgAPI: pad to the shard count,
        dummy id 0) wins in every Distributed* MRO."""
        return np.asarray(sampled, np.int64), len(sampled)

    def _place_cohort_rows(self, rows):
        """Spilled-store cohort rows -> device (mesh override shards them
        over the client axis)."""
        return jax.tree_util.tree_map(jnp.asarray, rows)

    def _place_batch(self, batch, round_rng):
        """Device placement hook — the sharded subclass pads the client axis
        to the mesh and shards these arrays over it."""
        return (
            jnp.asarray(batch.x),
            jnp.asarray(batch.y),
            jnp.asarray(batch.mask),
            jnp.asarray(batch.num_samples),
            round_client_rngs(round_rng, batch.num_clients),
        )

    def _round_plan(self, round_idx: int):
        """(sampled, steps, bs) of one round, memoized: the pipeline plans
        a round while the one before it runs, and the round, its health
        update and its logged row then ask again — the round-seeded
        sampling and the bucket math are done once."""
        plan = self._round_plans.get(round_idx)
        if plan is None:
            from fedml_tpu.data.base import bucket_steps

            cfg = self.config
            # its ``parent`` says who paid for the selection: ``prepare``
            # (a round early, hidden) or the round itself
            with self._tracer.span("select", round=round_idx) as sp:
                sampled = self._sample_clients(round_idx)
                steps, bs, _ = bucket_steps(
                    # an empty cohort (possible under DP's Poisson sampling) still
                    # needs a well-formed shape class — shape it like 1 sample
                    self._client_counts(sampled) if len(sampled) else [1],
                    cfg.data.batch_size,
                    cfg.data.pad_bucket,
                )
                sp.set_attr("clients", len(sampled))
            plan = (sampled, steps, bs)
            self._round_plans[round_idx] = plan
        return plan

    def _sample_clients(self, round_idx: int) -> np.ndarray:
        """This round's cohort draw, via the scheduler registry
        (FedConfig.selection; the default ``uniform`` policy is the
        reference-parity round-seeded fixed-size draw) — deterministic by
        design, so runs are reproducible and resumable, minus any clients
        the fault plan removes. Algorithms whose GUARANTEES depend on the
        randomness of participation override this (DP-FedAvg draws Poisson
        cohorts from a run-seeded secret stream: privacy amplification by
        subsampling is void if the adversary can predict who participated
        — privacy/dp_fedavg.py)."""
        sel = self.scheduler.select(round_idx)
        if self.faults is not None:
            sel = self._apply_participation_faults(sel, round_idx)
        return sel

    def _apply_participation_faults(self, selected, round_idx: int) -> np.ndarray:
        """Simulator fault semantics (scheduler/faults.py): dropout/crash
        remove the client from the cohort before batching. Memoized per
        round — the pipeline, train loop, and metric flush all
        re-derive the cohort, and the injector's counters must count each
        fault once. At least one survivor is kept so the round's jitted
        shapes stay well-formed."""
        r = int(round_idx)
        cached = self._fault_cache.get(r)
        if cached is not None:
            return cached
        decisions = [(int(cid), self.faults.decide(int(cid), r)) for cid in selected]
        survivors = [cid for cid, d in decisions if d.participates]
        spared = None
        if not survivors:
            # every selected client faulted: spare the first one so the
            # round stays well-formed — and do NOT record a fault for it
            # (it actually trains; accounting must describe what ran)
            spared = int(selected[0])
            survivors = [spared]
            import logging

            logging.warning(
                "fault plan removed the ENTIRE round-%d cohort; sparing "
                "client %d so the round stays well-formed", r, spared,
            )
        for cid, d in decisions:
            if cid == spared or d.participates:
                continue
            self.faults.record(cid, r, "crash" if d.crashed else "dropout")
        out = np.asarray(survivors, np.int64)
        self._fault_cache[r] = out
        return out

    def _log_round(self, round_idx: int, metrics) -> dict:
        cfg = self.config
        count = float(metrics["count"])
        row = {
            "round": round_idx,
            "Train/Loss": float(metrics["loss_sum"]) / max(count, 1e-9),
            "Train/Acc": float(metrics["correct"]) / max(count, 1e-9),
        }
        # feed power_of_choice: rounds whose program emitted per-client
        # loss vectors already reported TRUE per-client losses
        # (_report_client_losses — sim/transport parity); everything else
        # (mesh/hierarchical rounds) falls back to the
        # cohort mean reported to every participant
        if round_idx not in self._client_loss_rounds:
            for cid in self._round_plan(round_idx)[0]:
                self.scheduler.report_loss(int(cid), row["Train/Loss"])
        if self._is_eval_round(round_idx):
            with self._tracer.span("eval", round=round_idx):
                if cfg.fed.eval_on_clients:
                    local = self.local_test_on_all_clients(round_idx)
                    # local-train metrics describe ALL clients (not just this
                    # round's cohort) — override the cohort sums, ref schema
                    row.update(
                        {k: v for k, v in local.items() if k != "round"}
                    )
                else:
                    row["Test/Loss"], row["Test/Acc"] = self.evaluate_global()
        self.history.append(row)
        self.log_fn(row)
        return row

    def _is_eval_round(self, round_idx: int) -> bool:
        cfg = self.config
        return (
            round_idx % cfg.fed.frequency_of_the_test == 0
            or round_idx == cfg.fed.comm_round - 1
        )

    _METRIC_KEYS = ("correct", "count", "loss_sum", "steps")

    def _pack_metrics(self, metrics) -> "jnp.ndarray":
        """One round's metrics dict -> a [K] device vector (single dispatch,
        issued while the round itself is still in flight)."""
        # the model's device counters ride behind the fixed keys when the
        # local train reports them (a caller's own local train need not)
        keys = self._METRIC_KEYS + tuple(
            k for k in self.model.counters if k in metrics
        )
        return jnp.stack([jnp.asarray(metrics[k]) for k in keys])

    def _flush_pending(self, pending) -> dict:
        """Fetch all deferred per-round metrics in ONE device->host transfer
        and log them in order. Fetching per round costs a full host-device
        round-trip each time, which stalls the dispatch of the next round;
        rounds were already packed to device vectors as they completed, so
        the flush is one concat + one transfer."""
        final = {}
        if not pending:
            return final
        rounds, vectors = zip(*pending)
        with self._tracer.span(
            "flush", first_round=rounds[0], last_round=rounds[-1],
            rows=len(rounds),
        ) as flush:
            # with the model's own constants (empty where it has none to give)
            for name, value in self._flush_attrs.items():
                flush.set_attr(name, value)
            # the one device-to-host fetch: it returns when the device has
            # finished every round flushed here, so this is the wait, not
            # host work — and where a drained device idles. The stacking is
            # part of it: its dispatches queue behind the rounds in flight
            # and wait with them (on the v5e 70 ms a flush of 20 rounds).
            with self._tracer.span("flush_wait", rows=len(rounds)):
                host = np.asarray(jnp.stack(vectors))
            fixed = len(self._METRIC_KEYS)
            if host.shape[1] > fixed:
                # the model's device counters, summed over the rounds flushed here
                sums = host[:, fixed:].sum(axis=0, dtype=np.float64)
                for name, total in zip(self.model.counters, sums):
                    flush.set_attr(name, float(total))
            for r, vals in zip(rounds, host):
                final = self._log_round(r, dict(zip(self._METRIC_KEYS, vals)))
        pending.clear()
        return final

    def train(self) -> Dict[str, float]:
        cfg = self.config
        final = {}
        pending = []  # (round_idx, device metrics [K])
        for round_idx in range(self.start_round, cfg.fed.comm_round):
            # a round the pipeline prepared carries its measured
            # hidden-host-time as span attrs — the flight recorder
            # folds them into the round record (overlap_s), keeping
            # the phase accounting honest under overlap: this span's
            # broadcast phase is ~0 BECAUSE overlap_s was spent
            # during the previous round's device execution
            attrs = {}
            ov = self._pipeline_overlap.pop(round_idx, None)
            if ov is not None:
                attrs = {"overlap_s": round(ov, 6), "pipeline_depth": 1}
            with self._tracer.span("round", round=round_idx, **attrs) as sp:
                _, metrics = self.train_round(round_idx)
            # a handful of small dispatches that queue behind the round just
            # dispatched: where the device is the slower side, this is where
            # the host waits for a free slot in the device's queue
            with self._tracer.span("pack", round=round_idx):
                pending.append((round_idx, self._pack_metrics(metrics)))
            # round pipeline: the dispatched round is still executing on
            # device (async dispatch) — prepare the NEXT round's
            # cohort/batch/placement now, so its broadcast phase is host
            # time the device never waits for. Commit point: the
            # _warm_placed stash popped at the round boundary;
            # _pipeline_prepare degrades to serial for adaptive policies
            # and fault plans.
            self._pipeline_prepare(round_idx + 1)
            # health: the cohort trained as one program — every sampled
            # client shares the round's wall time (the ``round`` span's);
            # participation/last-seen are exact per client (_round_plan is
            # memoized, so this costs no re-sampling)
            dt = sp.dur_us / 1e6
            cohort = self._round_plan(round_idx)[0]
            with self._tracer.span(
                "health", first_round=round_idx, last_round=round_idx,
                clients=len(cohort),
            ):
                for cid in cohort:
                    self.health.observe_train(int(cid), round_idx, dt)
            # Flush on an eval round — eval must read global_vars exactly
            # as of that round, before the next one is dispatched. Also
            # flush periodically so history never lags far behind the
            # device.
            if self._is_eval_round(round_idx) or len(pending) >= 64:
                final = self._flush_pending(pending)
        final = self._flush_pending(pending) or final
        return final
