"""FedOpt family — FedAvg + a server-side optimizer (ref:
fedml_api/distributed/fedopt/ + fedml_api/standalone/fedopt/).

The reference aggregates like FedAvg, then writes the pseudo-gradient
``grad := w_old − w_avg`` into ``param.grad`` and calls a reflected
``torch.optim`` class (FedOptAggregator.py:95-117, OptRepo optrepo.py:7-50).
Here the same move is an optax transform applied to the pseudo-gradient — the
OptRepo reflection becomes a name→optax-constructor registry. Server state
(momentum/adaptivity) persists across rounds as an explicit optax state
pytree — the reference rebuilds the optimizer each round to preserve state
(FedOptAggregator.py:95-102); here it is just carried functionally.

Only the ``params`` collection goes through the server optimizer; non-param
collections (BatchNorm stats) are plain weighted averages, matching the
reference (state-dict averaging covers BN stats, FedAVGAggregator.py:66-71)."""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import optax

from fedml_tpu.config import RunConfig, ServerConfig
from fedml_tpu.data.base import FederatedDataset
from fedml_tpu.models import ModelDef
from fedml_tpu.algorithms.fedavg import FedAvgAPI, weighted_average


def make_server_optimizer(sc: ServerConfig) -> optax.GradientTransformation:
    """Name → optax constructor (ref OptRepo name→torch.optim class,
    optrepo.py:7-50; FedAdam/FedYogi per 'Adaptive Federated Optimization',
    the paper the reference's benchmark rows cite)."""
    name = sc.server_optimizer.lower()
    if name == "sgd":
        return optax.sgd(sc.server_lr)
    if name in ("momentum", "sgdm"):
        return optax.sgd(sc.server_lr, momentum=sc.server_momentum or 0.9)
    if name == "adam":
        return optax.adam(sc.server_lr, b1=0.9, b2=0.99, eps=sc.tau)
    if name == "yogi":
        return optax.yogi(sc.server_lr, b1=0.9, b2=0.99, eps=sc.tau)
    if name == "adagrad":
        return optax.adagrad(sc.server_lr, eps=sc.tau)
    raise ValueError(f"unknown server_optimizer {sc.server_optimizer!r}")


def make_server_step(opt: optax.GradientTransformation) -> Callable:
    """``(old_vars, avg_vars, opt_state) -> (new_vars, new_state)`` — the
    FedOpt server move, shared by the vmap/mesh APIs and the transport
    server manager so the pseudo-gradient math lives once."""

    def server_step(old_vars, avg_vars, opt_state):
        # pseudo-grad = w_old − w_avg (FedOptAggregator.py:109-117)
        pseudo_grad = jax.tree_util.tree_map(
            lambda o, a: o - a, old_vars["params"], avg_vars["params"]
        )
        updates, new_state = opt.update(
            pseudo_grad, opt_state, old_vars["params"]
        )
        new_params = optax.apply_updates(old_vars["params"], updates)
        new_vars = dict(avg_vars)  # non-param collections: plain average
        new_vars["params"] = new_params
        return new_vars, new_state

    return server_step


def make_cached_server_step(config: RunConfig):
    """THE jitted FedOpt server-step program, deduped through the
    process-wide ProgramCache — the one registration point shared by the
    vmap/mesh APIs and the transport server manager (both previously
    spelled the digest dict out by hand; a drift between the two copies
    would have split the program they are required to share). The step's
    CODE is fully determined by the server config — the param tree enters
    as a jit shape class, not a program determinant — so one jit object
    serves every model and every API instance in the process. Returns
    ``(cached_program, optimizer)``."""
    from fedml_tpu.compile import get_program_cache

    opt = make_server_optimizer(config.server)
    # step_builder marker MUST be the module-level make_server_step —
    # every call site keys the same program with it, so all sides dedup
    # onto ONE executable
    prog = get_program_cache().get_or_build(
        "server_opt",
        {
            "kind": "fedopt_server_step",
            "server": config.server,
            "step_builder": make_server_step,
        },
        lambda: jax.jit(make_server_step(opt)),
    )
    return prog, opt


class FedOptAPI(FedAvgAPI):
    """FedOpt simulator: FedAvgAPI with a server-optimizer step appended to
    each round (ref standalone/fedopt/fedopt_api.py:34-109)."""

    _donate = False  # train_round reads old_vars after the round call

    def __init__(self, config: RunConfig, data: FederatedDataset, model: ModelDef, **kw):
        super().__init__(config, data, model, **kw)
        self._server_step, self.server_opt = make_cached_server_step(config)
        self.server_opt_state = self.server_opt.init(self.global_vars["params"])

    def train_round(self, round_idx: int):
        old_vars = self.global_vars
        sampled, metrics = super().train_round(round_idx)
        # super() set global_vars to the plain weighted average; redo the
        # params through the server optimizer.
        self.global_vars, self.server_opt_state = self._server_step(
            old_vars, self.global_vars, self.server_opt_state
        )
        return sampled, metrics
