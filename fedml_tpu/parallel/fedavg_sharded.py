"""Sharded (multi-chip) FedAvg round — the distributed runtime.

The reference's distributed FedAvg is a server FSM + N client processes over
MPI, exchanging full state dicts as JSON lists each round (SURVEY §3.1:
FedAvgServerManager.py:34-72, message.py:47-59). Here the whole round is ONE
SPMD program over a `Mesh(("clients",))`:

- broadcast w_t   -> parameters enter `shard_map` with spec P() (replicated —
                     XLA materialises the broadcast over ICI once)
- local training  -> each shard vmaps the jitted local-train scan over its
                     C/n_shards clients (ref HOT LOOP #2)
- upload+aggregate-> weighted partial sums + `psum` over the client axis
                     (ref HOT LOOP #3, FedAVGAggregator.py:51-78's Python
                     per-key loop, and the MPI gather it sits on)

No host round-trip, no serialization, no 0.3 s poll loop
(mpi com_manager.py:71-80). Works identically on a virtual CPU mesh."""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.algorithms.fedavg import (
    FedAvgAPI,
    client_axis_map,
    resolve_client_parallelism,
    round_client_rngs,
)
from fedml_tpu.algorithms.fednova import FedNovaAPI
from fedml_tpu.algorithms.fedopt import FedOptAPI
from fedml_tpu.algorithms.ditto import DittoAPI
from fedml_tpu.algorithms.scaffold import ScaffoldAPI
from fedml_tpu.privacy.dp_fedavg import DPFedAvgAPI
from fedml_tpu.config import RunConfig
from fedml_tpu.data.base import ClientBatch, FederatedDataset
from fedml_tpu.models import ModelDef
from fedml_tpu.parallel.mesh import make_mesh, pad_client_batch
from fedml_tpu.train.client import make_local_train


def make_sharded_fedavg_round(
    model: ModelDef,
    config: RunConfig,
    mesh: Mesh,
    task: str = "classification",
    local_train_fn: Optional[Callable] = None,
    donate: bool = True,
    post_train: Optional[Callable] = None,
    post_aggregate: Optional[Callable] = None,
    aggregate_fn: Optional[Callable] = None,
    n_extra: int = 0,
    robust=None,
):
    """Build the jitted sharded round function.

    Returned fn: ``(global_vars, x, y, mask, num_samples, client_rngs,
    *extra) -> (global_vars', metrics)`` where the leading client axis of
    the data args is sharded over the mesh and C % mesh_size == 0 (use
    :func:`pad_client_batch`). ``client_rngs`` is [C, 2]-shaped PRNG key data,
    one key per client, so per-client randomness is identical regardless of
    mesh size (same-seed single-chip and 8-shard runs bit-match — the
    mesh-invariance test relies on this).

    The hook triple mirrors :func:`make_fedavg_round` exactly (same
    signatures, same semantics), so one defense/variant definition serves
    both runtimes. ``n_extra`` replicated trailing args (e.g. a noise rng)
    are forwarded to both hooks. ``aggregate_fn`` replaces the weighted
    psum; because the Byzantine aggregators are order statistics over the
    FULL client axis, the skeleton ``all_gather``s the client updates over
    ICI and hands the aggregate_fn the same stacked view the vmap runtime
    gives it — equality by construction."""
    axis = mesh.axis_names[0]
    if robust is not None:
        # describable defense config instead of opaque hook closures —
        # same contract as make_fedavg_round(robust=): the hooks derive
        # from the digested RobustConfig, so the robust SHARDED round is
        # a first-class cached program too
        if any(h is not None for h in (post_train, post_aggregate, aggregate_fn)):
            raise ValueError(
                "pass either robust= (describable defense config) or "
                "explicit hook closures, not both"
            )
        from fedml_tpu.algorithms.fedavg_robust import make_defense_hooks

        post_train, post_aggregate, aggregate_fn = make_defense_hooks(robust)
    # The client schedule matters on the mesh too: each shard runs its
    # C/n_shards clients, and under vmap their per-client weights turn the
    # convs into grouped convs (the single-chip 1.8x ResNet
    # finding). "scan" runs the shard's clients sequentially
    # with full MXU tiling. skip_empty_steps stays off here: lax.cond
    # branch types under shard_map's varying-axes rules don't admit the
    # constant-zero skip branch (padded steps remain where-gated no-ops).
    mode = resolve_client_parallelism(config.fed.client_parallelism, model)
    local_train = local_train_fn or make_local_train(
        model, config.train, config.fed.epochs, task=task
    )
    lifted = client_axis_map(local_train, mode)

    def shard_body(global_vars, x, y, mask, num_samples, client_rngs, *extra):
        # Params enter replicated (spec P()); mark them device-varying so the
        # local-train scan carry (params mixed with sharded data) type-checks
        # under shard_map's varying-manual-axes rules.
        global_vars = jax.tree_util.tree_map(
            lambda a: jax.lax.pcast(a, (axis,), to="varying"), global_vars
        )
        client_vars, metrics = lifted(global_vars, x, y, mask, client_rngs)
        if post_train is not None:
            client_vars = post_train(client_vars, global_vars, *extra)
        if aggregate_fn is not None:
            gathered = jax.tree_util.tree_map(
                lambda p: jax.lax.all_gather(p, axis, tiled=True), client_vars
            )
            ns_all = jax.lax.all_gather(num_samples, axis, tiled=True)
            new_global = aggregate_fn(gathered, ns_all, global_vars)
        else:
            # Weighted partial sum on this shard, then one psum over ICI.
            wsum = jax.lax.psum(jnp.sum(num_samples), axis)
            new_global = jax.tree_util.tree_map(
                lambda p: jax.lax.psum(
                    jnp.tensordot(num_samples, p.astype(jnp.float32), axes=1),
                    axis,
                )
                / wsum,
                client_vars,
            )
        if post_aggregate is not None:
            new_global = post_aggregate(new_global, *extra)
        agg_metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.psum(jnp.sum(m), axis), metrics
        )
        return new_global, agg_metrics

    data_spec = P(axis)

    def builder():
        sharded = jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(P(),) + (data_spec,) * 5 + (P(),) * n_extra,
            out_specs=(P(), P()),
            # the all_gather-ed aggregate is replicated by construction (every
            # shard reduces the same gathered stack), which static VMA
            # inference cannot see
            check_vma=aggregate_fn is None,
        )
        return jax.jit(sharded, donate_argnums=(0,) if donate else ())

    # Program dedup (fedml_tpu/compile/): sharded rounds are keyed by the
    # mesh topology on top of the usual (model, train config, schedule)
    # determinants; opaque hooks bypass the registry.
    from fedml_tpu.compile import (
        get_program_cache,
        hooks_cacheable,
        mesh_fingerprint,
        model_fingerprint,
    )

    cache = get_program_cache()
    cacheable = (
        hooks_cacheable(local_train_fn)
        if robust is not None
        else hooks_cacheable(
            local_train_fn, post_train, post_aggregate, aggregate_fn
        )
    )
    if not cacheable:
        return cache.wrap_uncached("sharded_fedavg_round", builder())
    return cache.get_or_build(
        "sharded_fedavg_round",
        {
            "kind": "sharded_fedavg_round",
            "model": model_fingerprint(model),
            "train": config.train,
            "epochs": config.fed.epochs,
            "task": task,
            "mode": mode,
            "mesh": mesh_fingerprint(mesh),
            "n_extra": n_extra,
            "donate": donate,
            # RobustConfig (or None) — see make_fedavg_round's digest note
            "robust": robust,
        },
        builder,
    )


class DistributedFedAvgAPI(FedAvgAPI):
    """Multi-chip FedAvg driver (ref FedML_FedAvg_distributed, FedAvgAPI.py:21-27
    + both manager classes). Subclass of the single-chip simulator: the host
    loop (sampling, stacking, metrics, eval) is inherited — including the
    scheduler-backed cohort selection and participation-fault filtering
    (FedConfig.selection/fault_plan, scheduler/): a fault-shrunk cohort is
    just another client-axis size, padded to the mesh like any ragged
    round — and this class only swaps the round function for the shard_map
    version and pads + places each round's batch sharded over the mesh."""

    _use_device_store = False  # batches are padded + sharded from host
    # the shard_map round psum-reduces its metrics — no per-client loss
    # vectors; power_of_choice keeps the cohort-mean signal on the mesh
    _client_loss_vectors = False

    def __init__(
        self,
        config: RunConfig,
        data: FederatedDataset,
        model: ModelDef,
        mesh: Optional[Mesh] = None,
        **kw,
    ):
        self.mesh = mesh or make_mesh(
            config.mesh.client_shards, config.mesh.axis_name
        )
        # pad to the number of shards along the CLIENT axis (the mesh may
        # carry more axes, e.g. a "seq" axis for sequence parallelism)
        self.n_shards = self.mesh.shape[self.mesh.axis_names[0]]
        self._data_sharding = NamedSharding(
            self.mesh, P(self.mesh.axis_names[0])
        )
        super().__init__(config, data, model, **kw)

    def _build_round_fn(self, local_train_fn):
        return make_sharded_fedavg_round(
            self.model,
            self.config,
            self.mesh,
            task=self.task,
            local_train_fn=local_train_fn,
            donate=self._donate,
        )

    def _pad_shard_indices(self, sampled):
        """Pad a sampled-client index vector to the mesh size and shard it
        — the gather/scatter vector of stateful algorithms (SCAFFOLD's
        control rows, Ditto's personal rows). Dummy rows point at client 0
        but train on all-zero masks, so their state deltas are EXACT zeros
        (the local-train step where-gates its whole update on has_data;
        pinned by tests) and the scatter-add ignores them."""
        ids, _ = self._spill_pad_ids(sampled)
        return jax.device_put(ids.astype(np.int32), self._data_sharding)

    def _spill_pad_ids(self, sampled):
        """(host ids padded to the shard count, real count) — ONE place
        owns the pad-to-mesh/dummy-id-0 contract, shared by the in-HBM
        index vector above and the spilled-store host gather/scatter
        (only the real prefix is ever scattered back)."""
        n = len(sampled)
        pad = (self.n_shards - n % self.n_shards) % self.n_shards
        ids = np.zeros((n + pad,), np.int64)
        ids[:n] = np.asarray(sampled, np.int64)
        return ids, n

    def _place_cohort_rows(self, rows):
        """Spilled-store cohort rows -> device, sharded over the client
        axis (stateful-algorithm spill x mesh composition)."""
        return jax.device_put(rows, self._data_sharding)

    def _place_batch(self, batch: ClientBatch, round_rng):
        """Pad the client axis to the mesh size and shard everything over it.
        Dummy (padding) clients get zero keys — their mask is all-zero so
        local training is a gated no-op and their aggregation weight is 0."""
        n_sampled = batch.num_clients
        batch = pad_client_batch(batch, self.n_shards)
        keys = np.asarray(round_client_rngs(round_rng, n_sampled))
        client_rngs = np.zeros(
            (batch.num_clients,) + keys.shape[1:], dtype=keys.dtype
        )
        client_rngs[:n_sampled] = keys
        put = lambda a: jax.device_put(a, self._data_sharding)
        return (
            put(batch.x),
            put(batch.y),
            put(batch.mask),
            put(batch.num_samples),
            put(client_rngs),
        )


class RobustDistributedFedAvgAPI(DistributedFedAvgAPI):
    """fedavg_robust on the multi-chip mesh runtime. Byzantine order
    statistics cannot silently include the zero dummy clients that client-
    axis padding would introduce, so the cohort must divide the mesh."""

    def __init__(self, config, data, model, robust=None, mesh=None, **kw):
        from fedml_tpu.robustness import BYZANTINE_AGGREGATORS, RobustConfig

        self.robust = robust or RobustConfig()
        super().__init__(config, data, model, mesh=mesh, **kw)
        if (
            self.robust.defense_type in BYZANTINE_AGGREGATORS
            and config.fed.client_num_per_round % self.n_shards
        ):
            raise ValueError(
                f"Byzantine aggregation on the mesh needs client_num_per_round "
                f"({config.fed.client_num_per_round}) divisible by the mesh "
                f"({self.n_shards}) — padded dummy clients would corrupt the "
                "order statistics"
            )

    def _build_round_fn(self, local_train_fn):
        return make_sharded_fedavg_round(
            self.model,
            self.config,
            self.mesh,
            task=self.task,
            local_train_fn=local_train_fn,
            donate=self._donate,
            robust=self.robust,
            n_extra=1,  # the replicated noise rng
        )

    def _place_batch(self, batch, round_rng):
        from fedml_tpu.algorithms.fedavg_robust import NOISE_FOLD

        base = super()._place_batch(batch, round_rng)
        return base + (jax.random.fold_in(round_rng, NOISE_FOLD),)


class DistributedDPFedAvgAPI(DPFedAvgAPI, DistributedFedAvgAPI):
    """Client-level DP-FedAvg on the multi-chip mesh runtime. Cooperative
    MRO: DPFedAvgAPI supplies the clip/noise hooks, the RDP ledger, and
    its checkpoint/reporting contract; DistributedFedAvgAPI supplies the
    mesh bootstrap and sharded batch placement (the noise rng rides the
    same _place_batch chain); this class swaps the round for the sharded
    skeleton with a psum uniform mean.

    Mesh padding is harmless here: the DP aggregate divides by the FIXED
    expected cohort and excludes padding rows via its num_samples
    inclusion mask (privacy/dp_fedavg.make_dp_hooks), so realized Poisson
    cohorts need not divide the mesh."""

    def __init__(self, config, data, model, dp=None, mesh=None, **kw):
        from fedml_tpu.privacy import DpConfig

        super().__init__(
            config, data, model, dp=dp or DpConfig(), mesh=mesh, **kw
        )

    def _build_round_fn(self, local_train_fn):
        from fedml_tpu.privacy.dp_fedavg import make_dp_hooks

        # the sharded skeleton all_gathers the full client stack before
        # calling aggregate_fn (same view as the vmap runtime), so the
        # single-chip fixed-denominator aggregate applies unchanged
        post_train, aggregate_fn, post_aggregate = make_dp_hooks(
            self.dp, self.config.fed.client_num_per_round
        )
        return make_sharded_fedavg_round(
            self.model,
            self.config,
            self.mesh,
            task=self.task,
            local_train_fn=local_train_fn,
            donate=self._donate,
            post_train=post_train,
            post_aggregate=post_aggregate,
            aggregate_fn=aggregate_fn,
            n_extra=1,  # the replicated noise rng
        )


class DistributedFedNovaAPI(FedNovaAPI, DistributedFedAvgAPI):
    """FedNova (normalized averaging) on the multi-chip mesh runtime — the
    reference's fednova is standalone-only. Cooperative MRO:
    DistributedFedAvgAPI supplies the mesh bootstrap + sharded batch
    placement; this class only swaps in the sharded FedNova round."""

    def _build_round_fn(self, local_train_fn):
        from fedml_tpu.algorithms.fednova import make_sharded_fednova_round

        return make_sharded_fednova_round(
            self.model,
            self.config,
            self.mesh,
            task=self.task,
            local_train_fn=local_train_fn,
            donate=self._donate,
        )


class DistributedScaffoldAPI(ScaffoldAPI, DistributedFedAvgAPI):
    """SCAFFOLD on the multi-chip mesh runtime (no reference counterpart —
    its SCAFFOLD doesn't exist at all; SURVEY §2b inventories FedNova as
    the closest). Cooperative MRO: DistributedFedAvgAPI supplies the mesh
    bootstrap and sharded batch placement; ScaffoldAPI supplies the
    control-variate state and train_round; this class swaps in the
    shard_map round and shards the gather/scatter index vector."""

    def _build_scaffold_round(self):
        from fedml_tpu.algorithms.scaffold import make_sharded_scaffold_round

        return make_sharded_scaffold_round(
            self.model, self.config, self.mesh, task=self.task
        )

    def _build_scaffold_cohort_round(self):
        from fedml_tpu.algorithms.scaffold import (
            make_sharded_scaffold_cohort_round,
        )

        return make_sharded_scaffold_cohort_round(
            self.model, self.config, self.mesh, task=self.task
        )

    def _place_client_indices(self, sampled):
        return self._pad_shard_indices(sampled)



class DistributedDittoAPI(DittoAPI, DistributedFedAvgAPI):
    """Ditto personalization on the multi-chip mesh runtime (no reference
    counterpart — its inventory has no personalization). Cooperative MRO:
    DistributedFedAvgAPI supplies the mesh bootstrap and sharded batch
    placement; DittoAPI supplies the personal store and train_round; this
    class swaps in the shard_map round and pads/shards the gather/scatter
    index vector (dummy rows train on all-zero masks and contribute
    exact-zero row deltas)."""

    def _build_ditto_round(self):
        from fedml_tpu.algorithms.ditto import make_sharded_ditto_round

        return make_sharded_ditto_round(
            self.model, self.config, self.mesh, self.lam, task=self.task,
            donate=self._donate,
        )

    def _build_ditto_cohort_round(self):
        from fedml_tpu.algorithms.ditto import make_sharded_ditto_cohort_round

        return make_sharded_ditto_cohort_round(
            self.model, self.config, self.mesh, self.lam, task=self.task
        )

    def _place_client_indices(self, sampled):
        return self._pad_shard_indices(sampled)



class DistributedFedOptAPI(FedOptAPI, DistributedFedAvgAPI):
    """FedOpt (server optimizer on the pseudo-gradient, ref
    FedOptAggregator.py:95-117) over the multi-chip mesh runtime.

    Cooperative MRO does all the work: FedOptAPI.train_round wraps the
    round with the jitted server step, DistributedFedAvgAPI supplies the
    shard_map round function and sharded batch placement. Donation is off
    (FedOptAPI._donate) because the server step reads the pre-round params
    after the round call."""
