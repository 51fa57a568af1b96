"""FedAvg-robust — FedAvg with backdoor defenses applied per-client before
averaging (ref: fedml_api/distributed/fedavg_robust/FedAvgRobustAggregator.py:
173-201; defense math in fedml_core/robustness/robust_aggregation.py).

The defense (norm-diff clipping, then optional weak-DP noise after the
average) runs inside the jitted round: clipping vmaps over the stacked client
axis instead of the reference's per-client Python loop. The poisoned-task
evaluation harness (backdoor accuracy, FedAvgRobustAggregator.py:14-60) pairs
with data/edge_cases.py's poisoned datasets."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.robustness import (
    RobustConfig,
    add_gaussian_noise,
    make_byzantine_aggregate,
    norm_diff_clip_tree,
)


# fold_in tag deriving the weak-DP noise key from the round rng — ONE
# definition, shared by the vmap and mesh APIs (their exact equality is a
# test contract, tests/test_robust_sharded.py)
NOISE_FOLD = 0x5EED


def make_defense_hooks(robust: RobustConfig):
    """defense config → (post_train, post_aggregate, aggregate_fn) — the
    hook triple both round skeletons (vmap make_fedavg_round, mesh
    make_sharded_fedavg_round) accept, so the defense math lives once."""

    def post_train(client_vars, global_vars, noise_rng):
        if robust.defense_type in ("norm_diff_clipping", "weak_dp"):
            return jax.vmap(
                lambda cv: norm_diff_clip_tree(cv, global_vars, robust.norm_bound)
            )(client_vars)
        return client_vars

    def post_aggregate(new_global, noise_rng):
        if robust.defense_type == "weak_dp":
            return add_gaussian_noise(new_global, noise_rng, robust.stddev)
        return new_global

    return post_train, post_aggregate, make_byzantine_aggregate(robust)


def make_robust_fedavg_round(
    model,
    config,
    robust: RobustConfig,
    task: str = "classification",
    local_train_fn=None,
    donate: bool = True,
):
    """The FedAvg round skeleton with the defense inserted via the
    DESCRIBABLE ``robust=`` path (the skeleton itself lives once, in
    make_fedavg_round): the round — including the Byzantine aggregators
    — dedupes through the ProgramCache with the RobustConfig in its
    digest, AOT-warms, and persists through the executable store like
    every other first-class program (it used to bypass via
    ``wrap_uncached`` because the hook closures were opaque)."""
    from fedml_tpu.algorithms.fedavg import make_fedavg_round

    return make_fedavg_round(
        model,
        config,
        task=task,
        local_train_fn=local_train_fn,
        donate=donate,
        robust=robust,
    )


class RobustFedAvgAPI(FedAvgAPI):
    """FedAvg simulator with robust aggregation."""

    def __init__(self, config, data, model, robust: RobustConfig = RobustConfig(), **kw):
        self.robust = robust
        super().__init__(config, data, model, **kw)

    def _build_round_fn(self, local_train_fn):
        inner = make_robust_fedavg_round(
            self.model,
            self.config,
            self.robust,
            task=self.task,
            local_train_fn=local_train_fn,
            donate=self._donate,
        )
        return inner

    def _place_batch(self, batch, round_rng):
        base = super()._place_batch(batch, round_rng)
        noise_rng = jax.random.fold_in(round_rng, NOISE_FOLD)
        return base + (noise_rng,)
