"""Ditto — personalized federated learning (Li et al., MLSys 2021).

BEYOND the reference's inventory (SURVEY §2b lists no personalization
algorithm): every client keeps a PERSONAL model v_k alongside the shared
global model w. The global model trains exactly as FedAvg; after each
local training, the sampled clients also advance their personal model by
SGD on the personalized objective

    min_v  F_k(v) + lam/2 * ||v - w||^2

i.e. the task loss plus a proximal pull toward the CURRENT global model
(w at round start — the model the server broadcast). lam interpolates
between purely-local models (lam=0: v_k never sees the federation) and
the global model (lam→inf: v_k pinned to w). Personalized accuracy is
evaluated per client: v_k on client k's own shard.

TPU-first shape (same pattern as SCAFFOLD's control store,
algorithms/scaffold.py): the N personal models live as ONE stacked
[N, ...] device pytree; a round gathers the sampled rows, runs the lifted
personal trains under the same vmap/scan client schedules as FedAvg, and
scatters the rows back — all inside one jitted round function.

Oracle discipline (tests/test_ditto.py): the personal-train loop mirrors
train/client.make_local_train's rng/permutation structure EXACTLY, so at
lam=0 a personal step sequence is bit-identical to plain local training —
the degenerate-config equality the CI oracle pattern demands
(ref CI-script-fedavg.sh:42-48).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algorithms.fedavg import (
    FedAvgAPI,
    client_axis_map,
    make_fedavg_round_body,
    resolve_client_parallelism,
)
from fedml_tpu.config import RunConfig, TrainConfig
from fedml_tpu.data.base import FederatedDataset
from fedml_tpu.models import ModelDef
from fedml_tpu.train.client import make_local_train


def make_ditto_personal_train(
    model: ModelDef, tc: TrainConfig, epochs: int, lam: float,
    task: str = "classification",
):
    """Personal-model training step:
    ``(w_ref_params, v_vars, x, y, mask, rng) -> (v_vars', metrics)``.

    This IS train/client.make_local_train with ``external_prox=True`` and
    prox_mu=lam: the one difference from plain local training is that the
    proximal term pulls toward the EXTERNAL ``w_ref_params`` (the
    broadcast global model) instead of the entry params — Ditto's
    personalized objective. Sharing the loop keeps the lam=0 case
    bit-identical to plain local training by construction."""
    return make_local_train(
        model,
        dataclasses.replace(tc, prox_mu=lam),
        epochs,
        task=task,
        external_prox=True,
    )


def make_ditto_round(
    model: ModelDef,
    config: RunConfig,
    lam: float,
    task: str = "classification",
    client_mode: Optional[str] = None,
    donate: bool = True,
):
    """Jitted Ditto round: plain-FedAvg global update + personal-row
    updates, one program.

    ``(global_vars, v_stack, idx, x, y, mask, num_samples, rngs) ->
      (global_vars', v_stack', metrics)``

    The personal step's proximal reference is the round-START global model
    (the broadcast w^t, per the paper's v-update), not the round's new
    average."""
    body = _make_ditto_cohort_body(model, config, lam, task, client_mode)

    def round_fn(global_vars, v_stack, idx, x, y, mask, num_samples, rngs):
        v_rows = jax.tree_util.tree_map(lambda s: s[idx], v_stack)
        new_global, new_rows, g_metrics = body(
            global_vars, v_rows, x, y, mask, num_samples, rngs
        )
        new_stack = jax.tree_util.tree_map(
            lambda s, r: s.at[idx].set(r), v_stack, new_rows
        )
        return new_global, new_stack, g_metrics

    # program dedup (fedml_tpu/compile/): fedlint uncached-jit caught this
    # factory returning a bare jit object — --warmup aside, every DittoAPI
    # construction over the same (model, config, lam) recompiled its own
    # round. lam is baked into the traced personal objective (prox_mu) as
    # a program CONSTANT, so it must split the digest.
    from fedml_tpu.compile import get_program_cache, model_fingerprint

    return get_program_cache().get_or_build(
        "ditto_round",
        {
            "kind": "ditto_round",
            "model": model_fingerprint(model),
            "train": config.train,
            "epochs": config.fed.epochs,
            "task": task,
            "lam": float(lam),
            "mode": client_mode,
            "parallelism": config.fed.client_parallelism,
            "donate": donate,
        },
        lambda: jax.jit(round_fn, donate_argnums=(1,) if donate else ()),
    )


def _make_ditto_cohort_body(model, config, lam, task, client_mode):
    """THE cohort-level Ditto round math — one definition shared by the
    full-stack round (which wraps it with the in-program idx
    gather/scatter) and the spilled cohort round (which jits it bare), so
    the two can never drift and spilled == in-HBM holds by construction
    (tests/test_state_spill.py)."""
    mode = client_mode or resolve_client_parallelism(
        config.fed.client_parallelism, model
    )
    fedavg_body = make_fedavg_round_body(
        model, config, task=task, client_mode=mode
    )
    personal = make_ditto_personal_train(
        model, config.train, config.fed.epochs, lam, task=task
    )
    lifted_personal = client_axis_map(personal, mode, n_broadcast=1)

    def body(global_vars, v_rows, x, y, mask, num_samples, rngs):
        new_global, (_, g_metrics) = fedavg_body(
            global_vars, x, y, mask, num_samples, rngs
        )
        # independent personal rng stream: same per-round keys, folded so
        # the global and personal shuffles/dropout draws are uncorrelated
        p_rngs = jax.vmap(lambda k: jax.random.fold_in(k, 0x0D17_70))(rngs)
        # personal metrics are dropped (nothing downstream reads them —
        # FedAvgAPI._pack_metrics consumes the global keys only; XLA DCEs
        # the unused computation), so the round's metrics are exactly the
        # FedAvg global-training metrics.
        new_rows, _ = lifted_personal(
            global_vars["params"], v_rows, x, y, mask, p_rngs
        )
        new_rows = jax.tree_util.tree_map(
            lambda r, old: r.astype(old.dtype), new_rows, v_rows
        )
        return new_global, new_rows, jax.tree_util.tree_map(jnp.sum, g_metrics)

    return body


def make_ditto_cohort_round(
    model: ModelDef,
    config: RunConfig,
    lam: float,
    task: str = "classification",
    client_mode: Optional[str] = None,
):
    """Cohort-form Ditto round for the SPILLED personal-model store:
    ``(global_vars, v_rows, x, y, mask, num_samples, rngs) ->
      (global_vars', v_rows', metrics)``
    — :func:`make_ditto_round` with the [N, ...] stack gather/scatter
    moved out to the host store (state_store.MmapClientState); only the
    cohort's [C, ...] personal rows enter HBM. Identical in-program math
    ⇒ spilled runs bit-match in-HBM runs (tests/test_state_spill.py)."""
    from fedml_tpu.compile import get_program_cache, model_fingerprint

    # donate the cohort rows (argnum 1): the host store keeps the durable copy
    return get_program_cache().get_or_build(
        "ditto_cohort_round",
        {
            "kind": "ditto_cohort_round",
            "model": model_fingerprint(model),
            "train": config.train,
            "epochs": config.fed.epochs,
            "task": task,
            "lam": float(lam),
            "mode": client_mode,
            "parallelism": config.fed.client_parallelism,
        },
        lambda: jax.jit(
            _make_ditto_cohort_body(model, config, lam, task, client_mode),
            donate_argnums=(1,),
        ),
    )


def make_sharded_ditto_cohort_round(
    model: ModelDef,
    config: RunConfig,
    mesh,
    lam: float,
    task: str = "classification",
):
    """Cohort-form Ditto round over a client-sharded mesh (the spill-tier
    x multi-chip composition — same shape as
    scaffold.make_sharded_scaffold_cohort_round): personal rows arrive
    SHARDED over the client axis straight from the host store's cohort
    gather and leave sharded for the scatter; the global FedAvg update is
    the weighted psum. Padded dummy rows (num_samples == 0, all-zero
    masks) contribute zero weight and unchanged rows."""
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    mode = resolve_client_parallelism(config.fed.client_parallelism, model)
    local_train = make_local_train(
        model, config.train, config.fed.epochs, task=task
    )
    lifted_local = client_axis_map(local_train, mode)
    personal = make_ditto_personal_train(
        model, config.train, config.fed.epochs, lam, task=task
    )
    lifted_personal = client_axis_map(personal, mode, n_broadcast=1)

    def shard_body(global_vars, v_rows, x, y, mask, num_samples, rngs):
        varying = lambda t: jax.tree_util.tree_map(
            lambda a: jax.lax.pcast(a, (axis,), to="varying"), t
        )
        gv = varying(global_vars)
        client_vars, metrics = lifted_local(gv, x, y, mask, rngs)
        wsum = jax.lax.psum(jnp.sum(num_samples), axis)
        w = num_samples / jnp.maximum(wsum, 1e-9)
        new_global = jax.tree_util.tree_map(
            lambda s: jax.lax.psum(
                jnp.tensordot(w, s.astype(jnp.float32), axes=1), axis
            ),
            client_vars,
        )
        p_rngs = jax.vmap(lambda k: jax.random.fold_in(k, 0x0D17_70))(rngs)
        new_rows, _ = lifted_personal(gv["params"], v_rows, x, y, mask, p_rngs)
        new_rows = jax.tree_util.tree_map(
            lambda r, old: r.astype(old.dtype), new_rows, v_rows
        )
        agg = jax.tree_util.tree_map(
            lambda m: jax.lax.psum(jnp.sum(m), axis), metrics
        )
        return new_global, new_rows, agg

    data_spec = P(axis)
    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(),) + (data_spec,) * 6,
        out_specs=(P(), data_spec, P()),
        check_vma=False,  # same stance as make_sharded_ditto_round
    )
    from fedml_tpu.compile import (
        get_program_cache,
        mesh_fingerprint,
        model_fingerprint,
    )

    return get_program_cache().get_or_build(
        "sharded_ditto_cohort_round",
        {
            "kind": "sharded_ditto_cohort_round",
            "model": model_fingerprint(model),
            "train": config.train,
            "epochs": config.fed.epochs,
            "task": task,
            "lam": float(lam),
            "parallelism": config.fed.client_parallelism,
            "mesh": mesh_fingerprint(mesh),
        },
        lambda: jax.jit(sharded, donate_argnums=(1,)),
    )


def make_sharded_ditto_round(
    model: ModelDef,
    config: RunConfig,
    mesh,
    lam: float,
    task: str = "classification",
    donate: bool = True,
):
    """Ditto round over a client-sharded mesh (shard_map form of
    make_ditto_round, same signature; no reference counterpart — the ref
    has no personalization at all).

    Sharding layout mirrors SCAFFOLD's (scaffold.make_sharded_scaffold_round):
    the personal store ``v_stack`` stays REPLICATED; the cohort's data and
    index vector shard over the client axis. Each shard gathers its own
    clients' personal rows, trains them against the replicated broadcast
    model, and the row updates travel as all_gathered COHORT deltas
    (O(|S|·params) over ICI) applied with ``.at[idx].add`` — dummy padding
    clients train on all-zero masks, end exactly where they started, and
    contribute exact-zero deltas, so idx collisions with padding rows are
    harmless."""
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    mode = resolve_client_parallelism(config.fed.client_parallelism, model)
    local_train = make_local_train(
        model, config.train, config.fed.epochs, task=task
    )
    lifted_local = client_axis_map(local_train, mode)
    personal = make_ditto_personal_train(
        model, config.train, config.fed.epochs, lam, task=task
    )
    lifted_personal = client_axis_map(personal, mode, n_broadcast=1)

    def shard_body(global_vars, v_stack, idx, x, y, mask, num_samples, rngs):
        varying = lambda t: jax.tree_util.tree_map(
            lambda a: jax.lax.pcast(a, (axis,), to="varying"), t
        )
        gv = varying(global_vars)
        stack = varying(v_stack)
        client_vars, metrics = lifted_local(gv, x, y, mask, rngs)
        wsum = jax.lax.psum(jnp.sum(num_samples), axis)
        w = num_samples / jnp.maximum(wsum, 1e-9)
        new_global = jax.tree_util.tree_map(
            lambda s: jax.lax.psum(
                jnp.tensordot(w, s.astype(jnp.float32), axes=1), axis
            ),
            client_vars,
        )
        v_rows = jax.tree_util.tree_map(lambda s: s[idx], stack)
        p_rngs = jax.vmap(lambda k: jax.random.fold_in(k, 0x0D17_70))(rngs)
        new_rows, _ = lifted_personal(gv["params"], v_rows, x, y, mask, p_rngs)
        delta = jax.tree_util.tree_map(
            lambda new, old: new.astype(old.dtype) - old, new_rows, v_rows
        )
        idx_all = jax.lax.all_gather(idx, axis, tiled=True)
        delta_all = jax.tree_util.tree_map(
            lambda d: jax.lax.all_gather(d, axis, tiled=True), delta
        )
        new_stack = jax.tree_util.tree_map(
            lambda stack_l, d: stack_l.at[idx_all].add(d), stack, delta_all
        )
        agg = jax.tree_util.tree_map(
            lambda m: jax.lax.psum(jnp.sum(m), axis), metrics
        )
        return new_global, new_stack, agg

    data_spec = P(axis)
    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(), P()) + (data_spec,) * 6,
        out_specs=(P(), P(), P()),
        # every output is psum/all_gather-combined, replicated by
        # construction; custom-VJP norm ops inside local_train defeat
        # static VMA inference (same stance as scaffold's sharded round)
        check_vma=False,
    )
    from fedml_tpu.compile import (
        get_program_cache,
        mesh_fingerprint,
        model_fingerprint,
    )

    return get_program_cache().get_or_build(
        "sharded_ditto_round",
        {
            "kind": "sharded_ditto_round",
            "model": model_fingerprint(model),
            "train": config.train,
            "epochs": config.fed.epochs,
            "task": task,
            "lam": float(lam),
            "parallelism": config.fed.client_parallelism,
            "mesh": mesh_fingerprint(mesh),
            "donate": donate,
        },
        lambda: jax.jit(sharded, donate_argnums=(1,) if donate else ()),
    )


class DittoAPI(FedAvgAPI):
    """Ditto simulator on the FedAvg skeleton — adds the per-client
    personal-model store and per-client personalized evaluation. The store
    is a stacked on-device [N, ...] pytree while it fits
    FedConfig.state_budget_bytes and SPILLS to the disk tier beyond it
    (state_store.MmapClientState; round 3 refused instead) — Ditto is
    cross-device by nature, so the spill path is the
    one that scales it to the data layer's 100k-client regime."""

    def __init__(
        self, config: RunConfig, data: FederatedDataset, model: ModelDef,
        lam: float = 0.1, **kw,
    ):
        super().__init__(config, data, model, **kw)
        from fedml_tpu.algorithms.state_store import (
            make_spill_store,
            resolve_state_store,
        )

        self.lam = float(lam)
        n = config.fed.client_num_in_total
        vbytes = sum(
            int(np.prod(v.shape)) * v.dtype.itemsize
            for v in jax.tree_util.tree_leaves(self.global_vars)
        )
        self._state_mode = resolve_state_store(
            config.fed, vbytes * n, n_clients=n,
            population=getattr(config, "population", None),
        )
        if self._state_mode == "device":
            # paper init: v_k = w_0 (every personal model starts at the
            # global init)
            self.v_stack = jax.tree_util.tree_map(
                lambda g: jnp.broadcast_to(g, (n,) + g.shape), self.global_vars
            )
            self._ditto_round = self._build_ditto_round()
        else:
            from fedml_tpu.algorithms.state_store import CohortPrefetcher

            self.v_stack = None
            # lazy v_k = w_0 init: untouched rows gather as w_0 without a
            # 100k-row write at construction
            self._v_store = make_spill_store(
                self._state_mode,
                jax.device_get(self.global_vars),
                n,
                config.fed.state_dir or None,
                population=getattr(config, "population", None),
            )
            self._v_prefetch = CohortPrefetcher(self._v_store)
            self._ditto_round = self._build_ditto_cohort_round()

    def _build_ditto_cohort_round(self):
        """Jitted cohort-form round for the SPILLED store. The mesh
        subclass swaps in the shard_map form — spill and multi-chip
        compose (round 4 refused here)."""
        return make_ditto_cohort_round(
            self.model, self.config, self.lam, task=self.task,
            client_mode=self._client_mode,
        )

    def _build_ditto_round(self):
        return make_ditto_round(
            self.model, self.config, self.lam, task=self.task,
            client_mode=self._client_mode, donate=self._donate,
        )

    def _build_round_fn(self, local_train_fn):
        return None  # unused — train_round is fully overridden

    def checkpoint_state(self):
        """Personal models are round state — a resume that dropped them
        would silently reset every client's personalization. Spilled-
        store checkpoints embed the touched rows themselves
        (self-contained npz); either representation restores into either
        store mode."""
        if self._state_mode == "device":
            return {"v_stack": self.v_stack}
        # self-contained: the touched rows ARE the store's whole
        # information content (untouched rows gather as w_0), so the
        # checkpoint survives tmp-cleaners and never references the live
        # (still-mutating) directory
        self._v_store.flush()  # checkpoint == durability point for the spill tier
        idx = self._v_store.initialized_ids()
        rows = self._v_store.gather(idx)
        out = {"v_rows_idx": idx}
        for i, leaf in enumerate(jax.tree_util.tree_leaves(rows)):
            out[f"v_rows_{i}"] = leaf
        return out

    def restore_state(self, tree):
        from fedml_tpu.utils.checkpoint import restore_like

        if self._state_mode != "device":
            # a pending prefetch holds PRE-restore rows; drop it before
            # reset_to rewrites the store
            self._v_prefetch.cancel()
        if "v_stack" in tree:
            if self._state_mode == "device":
                self.v_stack = restore_like(self.v_stack, tree["v_stack"])
            else:
                # a device-mode checkpoint restores into a spilled run by
                # scattering the whole stack
                stack = restore_like(
                    jax.tree_util.tree_map(
                        lambda g: jnp.broadcast_to(
                            g, (self._v_store.n,) + g.shape
                        ),
                        self.global_vars,
                    ),
                    tree["v_stack"],
                )
                self._v_store.reset_to(
                    np.arange(self._v_store.n), jax.device_get(stack)
                )
        else:
            idx = np.asarray(tree["v_rows_idx"])
            template = jax.device_get(self.global_vars)
            leaves, treedef = jax.tree_util.tree_flatten(template)
            rows = jax.tree_util.tree_unflatten(
                treedef,
                [np.asarray(tree[f"v_rows_{i}"]) for i in range(len(leaves))],
            )
            if self._state_mode == "device":
                # a spilled checkpoint restores into a device-mode run
                self.v_stack = jax.tree_util.tree_map(
                    lambda s, r: jnp.asarray(s).at[
                        jnp.asarray(idx)
                    ].set(jnp.asarray(r)),
                    jax.tree_util.tree_map(
                        lambda g: jnp.broadcast_to(
                            g,
                            (self.config.fed.client_num_in_total,) + g.shape,
                        ),
                        self.global_vars,
                    ),
                    rows,
                )
            else:
                self._v_store.reset_to(idx, rows)

    def _personal_row(self, i: int):
        """Client i's personal model as a single-row pytree — the one
        accessor personalized eval uses, store-agnostic."""
        if self._state_mode == "device":
            return jax.tree_util.tree_map(lambda s: s[i], self.v_stack)
        return jax.tree_util.tree_map(
            lambda r: r[0], self._v_store.gather([i])
        )

    def _place_client_indices(self, sampled):
        """The sampled client ids as the round fn's gather/scatter index
        vector — the sharded subclass pads to the mesh and shards it."""
        return jnp.asarray(np.asarray(sampled, np.int32))

    def train_round(self, round_idx: int):
        sampled, _steps, _bs = self._round_plan(round_idx)
        # batch via the shared warmup/pipeline stash contract (see
        # fedavg._round_placed — byte-identical to building it here)
        placed = self._round_placed(round_idx, sampled)
        if self._state_mode == "device":
            self.global_vars, self.v_stack, metrics = self._ditto_round(
                self.global_vars,
                self.v_stack,
                self._place_client_indices(sampled),
                *placed,
            )
            return sampled, metrics
        # NOTE: this take/launch/device_get/scatter choreography is the
        # same contract as ScaffoldAPI.train_round's spilled path (exclude
        # this round's ids from the background read; scatter only
        # rows[:n_real]) — tests/test_state_spill.py pins both against
        # their in-HBM twins, so a divergence fails loudly
        ids, n_real = self._spill_pad_ids(sampled)
        v_rows = self._place_cohort_rows(self._v_prefetch.take(round_idx, ids))
        self.global_vars, new_rows, metrics = self._ditto_round(
            self.global_vars,
            v_rows,
            *placed,
        )
        # overlap the next cohort's disk gather with this round's device
        # compute; rows scattered below are excluded (no torn reads)
        if round_idx + 1 < self.config.fed.comm_round:
            nxt_ids, _ = self._spill_pad_ids(self._round_plan(round_idx + 1)[0])
            self._v_prefetch.launch(
                round_idx + 1, nxt_ids,
                exclude=set(int(i) for i in np.asarray(sampled)),
            )
        host_rows = jax.device_get(new_rows)
        self._v_store.scatter(
            np.asarray(sampled),
            jax.tree_util.tree_map(lambda r: r[:n_real], host_rows),
        )
        return sampled, metrics

    def train(self):
        final = super().train()
        final = dict(final or {})
        personalized = self.personalized_test_on_clients()
        final.update(personalized)
        self.log_fn(personalized)
        return final

    def personalized_test_on_clients(
        self, batch_size: int = 256, max_clients: int = 256,
    ):
        """Per-client eval of each personal model on that client's OWN
        shard (test shard when present, else train shard) — Ditto's
        headline metric, vs the single global model on the same shards.
        Above ``max_clients`` clients a seeded subset is evaluated (two
        evals per client; unbounded N would dwarf the training loop)."""
        from fedml_tpu.train.evaluate import evaluate

        has_test = self.data.client_test_x is not None
        ids = range(self.data.num_clients)
        if self.data.num_clients > max_clients:
            ids = np.random.default_rng(self.config.seed).choice(
                self.data.num_clients, size=max_clients, replace=False
            )
        per_rows, g_rows = [], []
        for i in ids:
            x = (self.data.client_test_x if has_test else self.data.client_x)[i]
            y = (self.data.client_test_y if has_test else self.data.client_y)[i]
            if len(y) == 0:
                continue
            v_i = self._personal_row(i)
            _, acc_p = evaluate(
                self.model, v_i, x, y, batch_size=batch_size, task=self.task,
                eval_fn=self.eval_fn,
            )
            _, acc_g = evaluate(
                self.model, self.global_vars, x, y, batch_size=batch_size,
                task=self.task, eval_fn=self.eval_fn,
            )
            per_rows.append(float(acc_p))
            g_rows.append(float(acc_g))
        return {
            "Personalized/Acc": float(np.mean(per_rows)),
            "Global/Acc": float(np.mean(g_rows)),
            "num_clients_evaluated": len(per_rows),
        }
