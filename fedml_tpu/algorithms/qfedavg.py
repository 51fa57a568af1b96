"""q-FedAvg — fair federated aggregation (q-FFL, Li et al., ICLR 2020).

BEYOND the reference's inventory (SURVEY §2b has no fairness-aware
aggregation): plain FedAvg minimizes the average loss, which lets the
server trade a few clients' terrible models for many clients' good ones.
q-FFL reweights toward high-loss clients — minimizing
(1/q+1)·Σ F_k^{q+1} — so accuracy is distributed more uniformly across
the federation. q interpolates from plain FedAvg (q=0) toward minimax
fairness (q→∞).

The q-FedAvg update (paper's Algorithm 2, public; implemented fresh):

    g_k   = (w_t - w_k) / lr                 (the client's effective grad)
    Delta_k = F_k^q * g_k
    h_k   = q * F_k^{q-1} * ||g_k||^2 + F_k^q / lr
    w_{t+1} = w_t - (sum_k Delta_k) / (sum_k h_k)

where F_k is client k's TRAINING loss at the broadcast model w_t —
computed EXACTLY here: one forward pass over the client's shard at w_t
inside the jitted round, before local training (an earlier draft used
the mean loss over the whole local trajectory, which systematically
down-weights fast-learning clients; the paper's weights are defined at
w_t). At q=0 this reduces EXACTLY to the uniform mean of the
client models: Delta_k = g_k, h_k = 1/lr, so
w - lr/K * sum (w - w_k)/lr... = mean_k w_k — the degenerate-config
oracle tests/test_qfedavg.py pins.

TPU shape: the whole update is one jitted round — the F_k forward pass
rides the same lifted client schedule as the local trains (fused by XLA
into the round program); no host round-trip.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from fedml_tpu.algorithms.fedavg import (
    FedAvgAPI,
    client_axis_map,
    make_fedavg_round_body,
    resolve_client_parallelism,
)
from fedml_tpu.config import RunConfig
from fedml_tpu.models import ModelDef
from fedml_tpu.train.client import make_task_loss


def qfedavg_update(global_vars, client_vars, losses, lr: float, q: float):
    """One q-FedAvg server step from the stacked client results.

    ``client_vars``: [K, ...] stacked trees; ``losses``: [K] mean training
    loss per client. Pure — oracle-testable."""
    eps = 1e-10
    L = jnp.maximum(jnp.asarray(losses, jnp.float32), eps)
    deltas = jax.tree_util.tree_map(
        lambda g, cv: (
            g.astype(jnp.float32)[None] - cv.astype(jnp.float32)
        ) / lr,
        global_vars, client_vars,
    )
    # ||g_k||^2 over the full tree
    gsq = sum(
        jnp.sum(jnp.square(d), axis=tuple(range(1, d.ndim)))
        for d in jax.tree_util.tree_leaves(deltas)
    )  # [K]
    Lq = L**q
    h = q * (L ** (q - 1)) * gsq + Lq / lr  # [K]
    hsum = jnp.sum(h)

    def upd(g, d):
        num = jnp.tensordot(Lq, d, axes=1)  # sum_k F_k^q g_k
        return (g.astype(jnp.float32) - num / hsum).astype(g.dtype)

    return jax.tree_util.tree_map(upd, global_vars, deltas)


def make_qfedavg_round(
    model: ModelDef,
    config: RunConfig,
    q: float,
    task: str = "classification",
    client_mode: Optional[str] = None,
    donate: bool = True,
    local_train_fn=None,
):
    """Jitted q-FedAvg round: the plain round's lifted local trains, with
    the weighted average replaced by the q-FFL update driven by each
    client's mean training loss. Same signature as the FedAvg round fn."""
    body = make_fedavg_round_body(
        model, config, task=task, client_mode=client_mode,
        local_train_fn=local_train_fn,
    )
    lr = config.train.lr
    mode = client_mode or resolve_client_parallelism(
        config.fed.client_parallelism, model
    )
    task_loss = make_task_loss(task)

    def broadcast_loss(gv, xc, yc, mc):
        """Mean training loss of ONE client's shard at the broadcast model
        — the F_k(w_t) that q-FFL's weights are defined on."""

        def step(carry, inp):
            xb, yb, mb = inp
            logits, _ = model.apply(gv, xb, train=False)
            loss, _, total = task_loss(logits, yb, mb)
            return carry + jnp.stack([loss * total, total]), None

        sums, _ = jax.lax.scan(step, jnp.zeros(2), (xc, yc, mc))
        return sums[0] / jnp.maximum(sums[1], 1.0)

    lifted_loss = client_axis_map(broadcast_loss, mode)

    def round_fn(global_vars, x, y, mask, num_samples, client_rngs):
        # F_k at w_t BEFORE local training (XLA may still schedule both
        # passes together — no data dependence forces an ordering)
        losses = lifted_loss(global_vars, x, y, mask)
        _, (client_vars, metrics) = body(
            global_vars, x, y, mask, num_samples, client_rngs
        )
        new_global = qfedavg_update(global_vars, client_vars, losses, lr, q)
        return new_global, jax.tree_util.tree_map(jnp.sum, metrics)

    # program dedup (fedml_tpu/compile/): q and lr are baked into the
    # traced update as program CONSTANTS, so both must determine the
    # digest (q explicitly; lr rides in config.train) — the scaffold
    # server-constant lesson
    from fedml_tpu.compile import get_program_cache, model_fingerprint

    cache = get_program_cache()
    builder = lambda: jax.jit(round_fn, donate_argnums=(0,) if donate else ())
    if local_train_fn is not None:
        return cache.wrap_uncached("qfedavg_round", builder())
    return cache.get_or_build(
        "qfedavg_round",
        {
            "kind": "qfedavg_round",
            "model": model_fingerprint(model),
            "train": config.train,
            "epochs": config.fed.epochs,
            "task": task,
            "mode": mode,
            "q": float(q),
            "donate": donate,
        },
        builder,
    )


class QFedAvgAPI(FedAvgAPI):
    """q-FedAvg simulator on the FedAvg skeleton."""

    def __init__(self, config, data, model, q: float = 1.0, **kw):
        if config.train.client_optimizer != "sgd" or config.train.momentum:
            raise ValueError(
                "q-FedAvg's h_k normalizer is defined on plain-SGD local "
                "steps (the paper's L-estimate 1/lr) — got "
                f"{config.train.client_optimizer!r}, "
                f"momentum={config.train.momentum}"
            )
        self.q = float(q)
        super().__init__(config, data, model, **kw)

    def _build_round_fn(self, local_train_fn):
        return make_qfedavg_round(
            self.model, self.config, self.q, task=self.task,
            client_mode=self._client_mode, donate=self._donate,
            local_train_fn=local_train_fn,
        )
