"""Hierarchical (cloud-edge-device) FedAvg — ref:
fedml_api/standalone/hierarchical_fl/{trainer.py:43-69, group.py:24-46}.

Two-level aggregation: clients belong to groups (edge servers); each global
round, every group runs ``group_comm_round`` FedAvg sub-rounds over its
sampled clients starting from the global model, then the cloud averages group
models weighted by group sample counts. With group_comm_round=1 this is
exactly flat FedAvg — the reference's CI oracle for hierarchical FL under any
group split (CI-script-fedavg.sh:52-58), carried over as a test here.

On TPU the group loop maps to ICI-level psum per group + a cross-group
average; here groups run through the same jitted round function with the
group's clients stacked on the client axis. (The reference's version is
broken in the fork — trainer.py:6 imports a module that no longer exists,
SURVEY §2c.)"""

from __future__ import annotations

from typing import List, Sequence

import jax
import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgAPI, weighted_average


def assign_groups(num_clients: int, group_num: int, seed: int = 0) -> List[np.ndarray]:
    """Random balanced client→group assignment (ref trainer.py's
    client_indexes-per-group sampling)."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(num_clients)
    return [np.sort(g) for g in np.array_split(perm, group_num)]


def resolve_groups(groups, num_clients: int, group_num: int, seed: int) -> List[np.ndarray]:
    """Normalize an explicit group list or fall back to :func:`assign_groups`
    — the ONE definition both the host-loop and mesh hierarchical APIs use,
    so their group semantics can never diverge (their exact equality is a
    test contract, tests/test_hierarchical_sharded.py)."""
    if groups is not None:
        return [np.asarray(g) for g in groups]
    return assign_groups(num_clients, group_num, seed=seed)


class HierarchicalFedAvgAPI(FedAvgAPI):
    # train_round runs its own group loop and never consumes the
    # _round_placed stash — pipelining would leak prepared batches
    _supports_pipeline = False
    """Two-level FedAvg simulator. Reuses the inherited jitted round function
    for every group sub-round; only the orchestration differs."""

    # The global model is fed to several group sub-rounds; donation would
    # invalidate it after the first group.
    _donate = False
    # Group sub-rounds have ragged cohort sizes and their metric trees are
    # tree_map-summed across groups — per-client loss vectors would make
    # the leaves ragged; power_of_choice keeps the cohort-mean signal here.
    _client_loss_vectors = False

    def __init__(self, config, data, model, groups: Sequence[np.ndarray] = None, **kw):
        super().__init__(config, data, model, **kw)
        self.groups = resolve_groups(
            groups, data.num_clients, config.fed.group_num, config.seed
        )
        # program dedup (fedml_tpu/compile/): weighted_average is a pure
        # module-level fn — one jitted cross-group average per process
        # instead of one per API instance (fedlint uncached-jit catch)
        from fedml_tpu.compile import get_program_cache

        self._avg = get_program_cache().get_or_build(
            "hierarchical_cloud_avg",
            {"kind": "hierarchical_cloud_avg", "fn": weighted_average},
            lambda: jax.jit(weighted_average),
        )

    def _group_round(self, round_idx: int, gi: int, members, sampled_set):
        """One group's ``group_comm_round`` sub-rounds from the current
        global model: ``(w_group | None, weight, metrics | None)``. THE
        group-level math, shared by the in-process loop below and the
        cross-process gRPC bridge (parallel/hierarchical_bridge.py) so an
        edge-server process computes exactly what the simulator computes
        for its group — their equality is a test contract
        (tests/test_multihost_bridge.py)."""
        cfg = self.config
        g_clients = [int(c) for c in members if int(c) in sampled_set]
        if not g_clients:
            return None, 0, None
        w_group = self.global_vars
        metrics_acc = None
        for sub in range(cfg.fed.group_comm_round):
            batch = self._stack(
                g_clients,
                cfg.seed * 1_000_003 + round_idx * 131 + gi * 17 + sub,
            )
            rng = jax.random.fold_in(
                self.rng, (round_idx + 1) * 1009 + gi * 31 + sub
            )
            w_group, m = self.round_fn(
                w_group, *self._place_batch(batch, rng)
            )
            metrics_acc = (
                m
                if metrics_acc is None
                else jax.tree_util.tree_map(
                    lambda a, b: a + b, metrics_acc, m
                )
            )
        weight = sum(len(self.data.client_y[c]) for c in g_clients)
        return w_group, weight, metrics_acc

    def _cloud_average(self, group_vars, group_weights):
        """Cloud step: weighted average of group models; an all-empty
        round (every group missed the cohort — possible with explicit
        partial ``groups``) keeps the current global model. THE cloud
        math, shared with the cross-process bridge
        (parallel/hierarchical_bridge.py) like :meth:`_group_round` —
        bridge == simulator is an equality contract."""
        if not group_vars:
            return self.global_vars
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jax.numpy.stack(
                [jax.numpy.asarray(l) for l in leaves]
            ),
            *group_vars,
        )
        return self._avg(
            stacked,
            jax.numpy.asarray(group_weights, dtype=jax.numpy.float32),
        )

    def train_round(self, round_idx: int):
        cfg = self.config
        # scheduler-backed cohort (FedConfig.selection + fault plan) — the
        # same memoized draw the base API's _round_plan/_log_round see
        sampled = self._sample_clients(round_idx)
        sampled_set = set(int(i) for i in sampled)
        group_vars, group_weights, metrics_acc = [], [], None
        for gi, members in enumerate(self.groups):
            w_group, weight, m = self._group_round(
                round_idx, gi, members, sampled_set
            )
            if w_group is None:
                continue
            group_vars.append(w_group)
            group_weights.append(weight)
            metrics_acc = (
                m
                if metrics_acc is None
                else jax.tree_util.tree_map(lambda a, b: a + b, metrics_acc, m)
            )
        self.global_vars = self._cloud_average(group_vars, group_weights)
        return sampled, metrics_acc
