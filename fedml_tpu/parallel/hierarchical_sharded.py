"""Hierarchical (cloud-edge-device) FedAvg as ONE two-level SPMD program.

The reference's hierarchical FL (fedml_api/standalone/hierarchical_fl/
{trainer.py:43-69, group.py:24-46}) is a Python loop: per global round,
every group (edge server) runs ``group_comm_round`` FedAvg sub-rounds, then
the cloud averages group models by group sample counts. The host-loop analog
here is algorithms/hierarchical.py. This module is the mesh-native version:
the whole global round — every group's every sub-round — is a single jitted
``shard_map`` program over a 2-D ``Mesh((groups, clients))``:

- group sub-round aggregation = ``psum`` over the inner ``clients`` axis
  ONLY (frequent sync → rides ICI on a hybrid mesh, parallel/multihost.py);
- the cloud average = one ``psum`` over the outer ``groups`` axis per
  global round (rare sync → may ride DCN).

This is exactly the ICI/DCN mapping SURVEY §2g calls for ("maps naturally
to ICI-level psum + DCN-level cross-slice aggregation"). Groups whose
cohort is empty this round keep their model and carry zero weight — parity
with the host loop, which skips them.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.algorithms.fedavg import FedAvgAPI, round_client_rngs
from fedml_tpu.algorithms.hierarchical import resolve_groups
from fedml_tpu.config import RunConfig
from fedml_tpu.data.base import FederatedDataset, bucket_steps, stack_clients
from fedml_tpu.models import ModelDef
from fedml_tpu.train.client import make_local_train


def make_hierarchical_sharded_round(
    model: ModelDef,
    config: RunConfig,
    mesh: Mesh,
    task: str = "classification",
    local_train_fn: Optional[Callable] = None,
    donate: bool = True,
):
    """Build the jitted two-level round function.

    Returned fn: ``(global_vars, x, y, mask, num_samples, client_rngs) ->
    (global_vars', metrics)`` with x [R, G, C, S, B, *feat], y/mask/ns/rngs
    alike — R = group_comm_round sub-rounds, G groups (sharded over the
    outer mesh axis), C client slots per group (sharded over the inner
    axis; pad with mask-0/weight-0 dummies). Per-(group, sub-round) math is
    identical to the host loop's round function at matched batches."""
    gaxis, caxis = mesh.axis_names
    local_train = local_train_fn or make_local_train(
        model, config.train, config.fed.epochs, task=task
    )

    def shard_body(global_vars, x, y, mask, ns, rngs):
        # Params enter replicated; the scan carry becomes per-GROUP state
        # (varying over the group axis) but stays replicated within a group
        # — every sub-round ends in a psum over the client axis, so the
        # carry is clients-invariant by construction and only the group
        # axis needs the varying cast.
        global_vars = jax.tree_util.tree_map(
            lambda a: jax.lax.pcast(a, (gaxis,), to="varying"), global_vars
        )
        # local shapes carry a size-1 group dim (axis 1) — drop it
        sq = lambda a: a.reshape((a.shape[0],) + a.shape[2:])
        x, y, mask, ns, rngs = (sq(a) for a in (x, y, mask, ns, rngs))

        def sub_round(w_group, per):
            x_r, y_r, m_r, ns_r, k_r = per
            # the local-train scan mixes params with client-sharded data, so
            # params must be clients-varying inside the vmap; the psum below
            # clears that axis again before the carry update
            w_in = jax.tree_util.tree_map(
                lambda a: jax.lax.pcast(a, (caxis,), to="varying"), w_group
            )
            client_vars, mets = jax.vmap(
                local_train, in_axes=(None, 0, 0, 0, 0)
            )(w_in, x_r, y_r, m_r, k_r)
            wsum = jax.lax.psum(jnp.sum(ns_r), caxis)
            has = wsum > 0
            denom = jnp.maximum(wsum, 1e-9)
            w_group = jax.tree_util.tree_map(
                lambda p, old: jnp.where(
                    has,
                    jax.lax.psum(
                        jnp.tensordot(ns_r, p.astype(jnp.float32), axes=1),
                        caxis,
                    )
                    / denom,
                    old,
                ),
                client_vars,
                w_group,
            )
            # local per-shard metric sums only — psum is linear, so the
            # cross-shard reduction happens ONCE after the scan instead of
            # R times on the critical path (R cross-DCN latencies saved)
            return w_group, jax.tree_util.tree_map(jnp.sum, mets)

        w_group, mets = jax.lax.scan(
            sub_round, global_vars, (x, y, mask, ns, rngs)
        )
        mets = jax.tree_util.tree_map(
            lambda m: jax.lax.psum(
                jax.lax.psum(jnp.sum(m, axis=0), caxis), gaxis
            ),
            mets,
        )
        # Cloud aggregation: weight = the group's true sample count this
        # round (cohort is the same across sub-rounds; read sub-round 0) —
        # ref trainer.py:43-69 group-size-weighted average semantics.
        gw = jax.lax.psum(jnp.sum(ns[0]), caxis)
        total = jax.lax.psum(gw, gaxis)
        new_global = jax.tree_util.tree_map(
            lambda p: jax.lax.psum(p * gw, gaxis) / total, w_group
        )
        return new_global, mets

    spec = P(None, gaxis, caxis)
    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(), spec, spec, spec, spec, spec),
        out_specs=(P(), P()),
    )

    # program dedup (fedml_tpu/compile/): fedlint uncached-jit caught this
    # factory returning a bare jit object. The sub-round count R and the
    # group/client axis sizes are SHAPE classes (they ride in on the
    # [R, G, C, ...] batch), not program constants — the mesh fingerprint
    # pins the topology. An opaque local_train_fn bypasses the registry.
    from fedml_tpu.compile import (
        get_program_cache,
        mesh_fingerprint,
        model_fingerprint,
    )

    cache = get_program_cache()
    builder = lambda: jax.jit(sharded, donate_argnums=(0,) if donate else ())
    if local_train_fn is not None:
        return cache.wrap_uncached("hierarchical_sharded_round", builder())
    return cache.get_or_build(
        "hierarchical_sharded_round",
        {
            "kind": "hierarchical_sharded_round",
            "model": model_fingerprint(model),
            "train": config.train,
            "epochs": config.fed.epochs,
            "task": task,
            "mesh": mesh_fingerprint(mesh),
            "donate": donate,
        },
        builder,
    )


class HierarchicalShardedAPI(FedAvgAPI):
    """Two-level FedAvg on a 2-D (groups × clients) mesh.

    Drop-in peer of algorithms/hierarchical.py's host-loop API: same
    round-seeded sampling, same group assignment, same per-(group,
    sub-round) stacking seeds and PRNG streams — so the two produce the
    same models/metrics (the equality test), but here a global round is one
    device program with no host round-trips between sub-rounds."""

    _use_device_store = False
    # group-loop train_round never consumes the _round_placed stash
    _supports_pipeline = False
    _donate = True

    def __init__(
        self,
        config: RunConfig,
        data: FederatedDataset,
        model: ModelDef,
        mesh: Optional[Mesh] = None,
        groups: Sequence[np.ndarray] = None,
        **kw,
    ):
        if mesh is None:
            from fedml_tpu.parallel.multihost import hybrid_mesh

            mesh = hybrid_mesh(
                "groups", "clients", dcn_size=config.fed.group_num
            )
        self.mesh = mesh
        gaxis, caxis = mesh.axis_names
        self.n_groups = mesh.shape[gaxis]
        self.n_client_shards = mesh.shape[caxis]
        self._data_sharding = NamedSharding(mesh, P(None, gaxis, caxis))
        super().__init__(config, data, model, **kw)
        self.groups = resolve_groups(
            groups, data.num_clients, self.n_groups, config.seed
        )
        if len(self.groups) != self.n_groups:
            raise ValueError(
                f"{len(self.groups)} groups != mesh group axis {self.n_groups}"
            )

    def _build_round_fn(self, local_train_fn):
        return make_hierarchical_sharded_round(
            self.model,
            self.config,
            self.mesh,
            task=self.task,
            local_train_fn=local_train_fn,
            donate=self._donate,
        )

    def train_round(self, round_idx: int):
        cfg = self.config
        R = cfg.fed.group_comm_round
        # scheduler-backed cohort (FedConfig.selection + fault plan),
        # memoized — identical to what the host-loop hierarchical API and
        # the base _round_plan derive for this round
        sampled = self._sample_clients(round_idx)
        sampled_set = set(int(i) for i in sampled)
        cohorts = [
            [int(c) for c in members if int(c) in sampled_set]
            for members in self.groups
        ]
        # one static shape across every group: bucket over the whole round's
        # cohort, pad group client slots to a multiple of the client shards.
        # Full-batch (-1) resolves to the round's max client size so every
        # group shares it (per-group -1 would give ragged bs); a bigger
        # single batch is identical math — the loss is a masked mean.
        all_ns = [len(self.data.client_y[i]) for i in sampled]
        steps, bs, _ = bucket_steps(all_ns, cfg.data.batch_size, cfg.data.pad_bucket)
        if cfg.data.batch_size == -1:
            # re-bucket with the resolved bs so steps follows the same
            # size-class rule stack_clients will apply per group
            steps, bs, _ = bucket_steps(all_ns, bs, cfg.data.pad_bucket)
        cmax = max(max((len(g) for g in cohorts), default=1), 1)
        rem = cmax % self.n_client_shards
        cmax += self.n_client_shards - rem if rem else 0

        feat = self.data.client_x[0].shape[1:]
        lab = self.data.client_y[0].shape[1:]
        G = self.n_groups
        x = np.zeros(
            (R, G, cmax, steps, bs) + feat, dtype=self.data.client_x[0].dtype
        )
        y = np.zeros(
            (R, G, cmax, steps, bs) + lab, dtype=self.data.client_y[0].dtype
        )
        mask = np.zeros((R, G, cmax, steps, bs), dtype=np.float32)
        ns = np.zeros((R, G, cmax), dtype=np.float32)
        key_shape = np.asarray(jax.random.PRNGKey(0)).shape
        key_dtype = np.asarray(jax.random.PRNGKey(0)).dtype
        rngs = np.zeros((R, G, cmax) + key_shape, dtype=key_dtype)
        for gi, g_clients in enumerate(cohorts):
            if not g_clients:
                continue
            n_g = len(g_clients)
            for sub in range(R):
                # exact seed/rng parity with the host-loop API
                # (algorithms/hierarchical.py train_round)
                batch = stack_clients(
                    self.data,
                    g_clients,
                    bs,  # resolved batch size (uniform across groups)
                    seed=cfg.seed * 1_000_003 + round_idx * 131 + gi * 17 + sub,
                    pad_bucket=cfg.data.pad_bucket,
                    force_steps=steps,
                )
                rng = jax.random.fold_in(
                    self.rng, (round_idx + 1) * 1009 + gi * 31 + sub
                )
                x[sub, gi, :n_g] = batch.x
                y[sub, gi, :n_g] = batch.y
                mask[sub, gi, :n_g] = batch.mask
                ns[sub, gi, :n_g] = batch.num_samples
                rngs[sub, gi, :n_g] = np.asarray(
                    round_client_rngs(rng, n_g)
                )
        put = lambda a: jax.device_put(a, self._data_sharding)
        self.global_vars, metrics = self.round_fn(
            self.global_vars, put(x), put(y), put(mask), put(ns), put(rngs)
        )
        return sampled, metrics
