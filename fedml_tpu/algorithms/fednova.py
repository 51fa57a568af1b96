"""FedNova — normalized averaging (ref: fedml_api/standalone/fednova/,
vendored from JYWa/FedNova; fednova.py:10 `FedNova(Optimizer)` with the
`local_normalizing_vec` bookkeeping at :141-170, server aggregation
`FedNovaTrainer.aggregate(params, norm_grads, tau_effs)` at
fednova_trainer.py:97-125).

Clients run heterogeneous numbers of local steps τ_i (ragged shards ⇒ ragged
step counts); plain FedAvg then implicitly over-weights fast clients. FedNova
normalizes each client's cumulative update by its step-accumulation factor
a_i and rescales by the effective τ:

    d_i   = (w_g − w_i) / a_i
    τ_eff = Σ p_i a_i          (p_i = n_i / Σ n)
    w'    = w_g − τ_eff Σ p_i d_i

For vanilla SGD a_i = τ_i; for local momentum ρ, a_i = Σ_{k=1}^{τ_i}
(1−ρ^k)/(1−ρ) = (τ_i − ρ(1−ρ^{τ_i})/(1−ρ))/(1−ρ) — exactly what the
reference's optimizer accumulates step-by-step into `local_normalizing_vec`
(fednova.py:141-170); here it's the closed form of τ_i, which the local-train
scan reports as the "steps" metric (all-padding steps are gated no-ops and
excluded). Unlike the reference (whose fednova is standalone-only), the same
round function vmaps on one chip and shard_maps over a mesh."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.train.client import make_local_train


def _accum_factor(tau, momentum: float):
    """Closed form of the reference's local_normalizing_vec after tau steps."""
    if momentum:
        rho = momentum
        return (tau - rho * (1.0 - rho**tau) / (1.0 - rho)) / (1.0 - rho)
    return tau


def _validate_and_build(model, config, task, local_train_fn):
    """Shared guard + local-train construction for BOTH FedNova round
    factories (vmap and mesh), so the supported-optimizer surface can
    never diverge between them. The closed-form a_i models plain/momentum
    SGD only; the reference's mu-aware accumulation (fednova.py etamu
    branch) and adaptive client optimizers are not modeled — reject rather
    than silently mis-normalize."""
    if config.train.client_optimizer != "sgd":
        raise ValueError(
            "FedNova requires client_optimizer='sgd' "
            f"(got {config.train.client_optimizer!r})"
        )
    if config.train.prox_mu:
        raise ValueError("FedNova with prox_mu is not supported")
    local_train = local_train_fn or make_local_train(
        model, config.train, config.fed.epochs, task=task
    )
    return local_train, config.train.momentum


def make_fednova_round(model, config, task="classification", local_train_fn=None, donate=True):
    local_train, momentum = _validate_and_build(model, config, task, local_train_fn)

    def round_fn(global_vars, x, y, mask, num_samples, client_rngs):
        client_vars, metrics = jax.vmap(
            local_train, in_axes=(None, 0, 0, 0, 0)
        )(global_vars, x, y, mask, client_rngs)
        p = num_samples / jnp.sum(num_samples)
        tau = metrics["steps"]  # [C] effective local steps
        a = _accum_factor(tau, momentum)
        # Dummy padded clients: tau = 0 ⇒ a = 0; their p is also 0 — guard
        # the division so 0/0 doesn't poison the sum.
        a_safe = jnp.where(a > 0, a, 1.0)
        tau_eff = jnp.sum(p * a)

        def nova_avg(stacked, g):
            stacked = stacked.astype(jnp.float32)
            # d_i = (w_g − w_i)/a_i ; w' = w_g − τ_eff Σ p_i d_i
            coeff = p * tau_eff / a_safe * (a > 0)
            return g - jnp.tensordot(coeff, g[None] - stacked, axes=1)

        # Only params get the nova update; other collections (BN stats) are
        # plain weighted averages as in FedAvg.
        new_params = jax.tree_util.tree_map(
            lambda s, g: nova_avg(s, g), client_vars["params"], global_vars["params"]
        )
        new_global = {
            k: (
                new_params
                if k == "params"
                else jax.tree_util.tree_map(
                    lambda s: jnp.tensordot(p, s.astype(jnp.float32), axes=1),
                    v,
                )
            )
            for k, v in client_vars.items()
        }
        agg_metrics = jax.tree_util.tree_map(jnp.sum, metrics)
        return new_global, agg_metrics

    # program dedup (fedml_tpu/compile/): one jitted FedNova round per
    # (model, train config, epochs, task) per process
    from fedml_tpu.compile import get_program_cache, model_fingerprint

    cache = get_program_cache()
    builder = lambda: jax.jit(round_fn, donate_argnums=(0,) if donate else ())
    if local_train_fn is not None:
        return cache.wrap_uncached("fednova_round", builder())
    return cache.get_or_build(
        "fednova_round",
        {
            "kind": "fednova_round",
            "model": model_fingerprint(model),
            "train": config.train,
            "epochs": config.fed.epochs,
            "task": task,
            "donate": donate,
        },
        builder,
    )


class FedNovaAPI(FedAvgAPI):
    """FedNova simulator — FedAvg round skeleton with normalized averaging."""

    def _build_round_fn(self, local_train_fn):
        return make_fednova_round(
            self.model,
            self.config,
            task=self.task,
            local_train_fn=local_train_fn,
            donate=self._donate,
        )


def make_sharded_fednova_round(model, config, mesh, task="classification", local_train_fn=None, donate=True):
    """The FedNova round over a client-sharded mesh: p-normalization,
    τ_eff, and the normalized-update tensordot become partial sums + one
    psum each over ICI. Math identical to :func:`make_fednova_round`
    (the mesh-vs-vmap parity test covers it)."""
    from jax.sharding import PartitionSpec as P

    local_train, momentum = _validate_and_build(model, config, task, local_train_fn)
    axis = mesh.axis_names[0]

    def shard_body(global_vars, x, y, mask, num_samples, client_rngs):
        # keep the replicated (invariant) view for the aggregation: the
        # final w' = g − psum(...) must be invariant for out_spec P(); the
        # varying cast is only needed where params mix with sharded data
        g_inv = global_vars
        global_vars = jax.tree_util.tree_map(
            lambda a: jax.lax.pcast(a, (axis,), to="varying"), global_vars
        )
        client_vars, metrics = jax.vmap(
            local_train, in_axes=(None, 0, 0, 0, 0)
        )(global_vars, x, y, mask, client_rngs)
        p = num_samples / jax.lax.psum(jnp.sum(num_samples), axis)
        tau = metrics["steps"]
        a = _accum_factor(tau, momentum)
        a_safe = jnp.where(a > 0, a, 1.0)
        tau_eff = jax.lax.psum(jnp.sum(p * a), axis)
        coeff = p * tau_eff / a_safe * (a > 0)

        def nova_avg(stacked, g):
            stacked = stacked.astype(jnp.float32)
            return g - jax.lax.psum(
                jnp.tensordot(coeff, g[None] - stacked, axes=1), axis
            )

        new_params = jax.tree_util.tree_map(
            nova_avg, client_vars["params"], g_inv["params"]
        )
        new_global = {
            k: (
                new_params
                if k == "params"
                else jax.tree_util.tree_map(
                    lambda s: jax.lax.psum(
                        jnp.tensordot(p, s.astype(jnp.float32), axes=1), axis
                    ),
                    v,
                )
            )
            for k, v in client_vars.items()
        }
        agg_metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.psum(jnp.sum(m), axis), metrics
        )
        return new_global, agg_metrics

    spec = P(axis)
    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(),) + (spec,) * 5,
        out_specs=(P(), P()),
    )

    # program dedup (fedml_tpu/compile/): keyed like the sharded FedAvg
    # round — the mesh fingerprint is part of the program's identity
    from fedml_tpu.compile import (
        get_program_cache,
        mesh_fingerprint,
        model_fingerprint,
    )

    cache = get_program_cache()
    builder = lambda: jax.jit(sharded, donate_argnums=(0,) if donate else ())
    if local_train_fn is not None:
        return cache.wrap_uncached("sharded_fednova_round", builder())
    return cache.get_or_build(
        "sharded_fednova_round",
        {
            "kind": "sharded_fednova_round",
            "model": model_fingerprint(model),
            "train": config.train,
            "epochs": config.fed.epochs,
            "task": task,
            "mesh": mesh_fingerprint(mesh),
            "donate": donate,
        },
        builder,
    )


# The mesh-runtime driver (DistributedFedNovaAPI) lives in
# parallel/fedavg_sharded.py next to its FedAvg/FedOpt siblings.
