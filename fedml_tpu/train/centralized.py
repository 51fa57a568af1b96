"""Centralized (non-federated) data-parallel trainer.

The reference's centralized baseline is a torch DDP/NCCL loop
(fedml_experiments/centralized/main.py:54-67,123 — one process per GPU,
`DistributedDataParallel` wrapping, `DistributedSampler.set_epoch` reshuffle,
fedml_api/centralized/centralized_trainer.py:43-45). The TPU-native analog
needs no process groups or gradient hooks: the train step is jitted with the
batch axis sharded over a `jax.sharding.Mesh` and params replicated — XLA
inserts the gradient all-reduce over ICI itself. One code path serves
single-chip and pod-scale DP.

This is also the non-federated accuracy baseline the benchmark compares
against, and the "centralized" side of the
federated==centralized oracle as a reusable component instead of test-inline
code."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.config import RunConfig
from fedml_tpu.models import ModelDef
from fedml_tpu.train.client import (
    build_client_optimizer,
    make_mixed_forward,
    make_task_loss,
)
from fedml_tpu.train.evaluate import make_eval_fn, pad_to_batches


def make_centralized_epoch(
    model: ModelDef,
    config: RunConfig,
    task: str = "classification",
    mesh: Optional[Mesh] = None,
    batch_axis: Optional[str] = None,
):
    """Build the jitted one-epoch trainer.

    Returned fn: ``(params, extra, opt_state, x, y, mask, rng) ->
    (params', extra', opt_state', metrics)`` with x [S, B, *feat] — a
    `lax.scan` of optimizer steps over the S pre-batched minibatches.
    Unlike the per-client local-train scan (train/client.py), optimizer
    state is an explicit carry so momentum/Adam moments persist across
    epochs (the centralized semantics the reference gets from a long-lived
    torch optimizer, centralized_trainer.py).

    With ``mesh``, the batch dimension B is sharded over ``batch_axis``
    (default: the mesh's first axis) and params are replicated — plain DP;
    XLA emits the psum for the gradient reduction."""
    tc = config.train
    opt = build_client_optimizer(tc)
    task_loss = make_task_loss(task)
    fwd = make_mixed_forward(model, tc)

    def loss_fn(params, extra, xb, yb, mb, step_rng):
        logits, new_extra = fwd(params, extra, xb, step_rng)
        loss, correct, total = task_loss(logits, yb, mb)
        return loss, (new_extra, correct, total)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def epoch_fn(params, extra, opt_state, x, y, mask, rng):
        def step(carry, inp):
            params, extra, opt_state = carry
            xb, yb, mb, sidx = inp
            (loss, (extra, correct, total)), grads = grad_fn(
                params, extra, xb, yb, mb, jax.random.fold_in(rng, sidx)
            )
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, extra, opt_state), jnp.stack(
                [loss * total, correct, total]
            )

        S = mask.shape[0]
        (params, extra, opt_state), mets = jax.lax.scan(
            step, (params, extra, opt_state), (x, y, mask, jnp.arange(S))
        )
        sums = mets.sum(axis=0)
        metrics = {"loss_sum": sums[0], "correct": sums[1], "count": sums[2]}
        return params, extra, opt_state, metrics

    if mesh is None:
        return jax.jit(epoch_fn, donate_argnums=(0, 1, 2))
    axis = batch_axis or mesh.axis_names[0]
    rep = NamedSharding(mesh, P())
    data_sh = NamedSharding(mesh, P(None, axis))  # [S, B, ...]: shard B
    return jax.jit(
        epoch_fn,
        in_shardings=(rep, rep, rep, data_sh, data_sh, data_sh, rep),
        out_shardings=(rep, rep, rep, rep),
        donate_argnums=(0, 1, 2),
    )


class CentralizedTrainer:
    """Pooled-data trainer over an optional device mesh (ref
    fedml_api/centralized/centralized_trainer.py + centralized/main.py).

    Pools all client shards (``FederatedDataset.centralized_train``),
    reshuffles per epoch with an epoch-seeded PRNG (the reference's
    ``sampler.set_epoch`` determinism, centralized_trainer.py:43-45), and
    runs the jitted DP epoch."""

    def __init__(
        self,
        config: RunConfig,
        data,
        model: ModelDef,
        task: str = "classification",
        mesh: Optional[Mesh] = None,
        log_fn=None,
    ):
        self.config, self.model, self.task, self.mesh = config, model, task, mesh
        self.data = data
        self.log_fn = log_fn or (lambda row: None)
        x, y = data.centralized_train()
        self._x = np.asarray(x)
        self._y = np.asarray(y)
        n_dev = 1 if mesh is None else int(np.prod(list(mesh.shape.values())))
        bs = config.data.batch_size
        if bs == -1:
            bs = len(self._x)  # full batch
        # batch must tile over the mesh; round up and let the mask pad
        self.batch_size = -(-bs // n_dev) * n_dev
        self.epoch_fn = make_centralized_epoch(model, config, task, mesh)
        self.eval_fn = make_eval_fn(model, task)
        variables = model.init(
            jax.random.fold_in(jax.random.PRNGKey(config.seed), 0)
        )
        self.params = variables["params"]
        self.extra = {k: v for k, v in variables.items() if k != "params"}
        self.opt_state = build_client_optimizer(config.train).init(self.params)
        self._rng = jax.random.PRNGKey(config.seed)

    @property
    def global_vars(self):
        return {"params": self.params, **self.extra}

    def train_epoch(self, epoch: int) -> dict:
        rng = np.random.default_rng((self.config.seed, epoch))
        perm = rng.permutation(len(self._x))
        x, y, mask = pad_to_batches(
            self._x[perm], self._y[perm], self.batch_size
        )
        self.params, self.extra, self.opt_state, metrics = self.epoch_fn(
            self.params,
            self.extra,
            self.opt_state,
            x,
            y,
            mask,
            jax.random.fold_in(self._rng, epoch),
        )
        count = float(metrics["count"])
        return {
            "epoch": epoch,
            "Train/Loss": float(metrics["loss_sum"]) / max(count, 1.0),
            "Train/Acc": float(metrics["correct"]) / max(count, 1.0),
        }

    def evaluate(self) -> Tuple[float, float]:
        # cap the eval batch: under batch_size=-1 (full train batch) padding
        # the test set to train-set size would waste compute / blow HBM
        x, y, mask = pad_to_batches(
            np.asarray(self.data.test_x),
            np.asarray(self.data.test_y),
            max(min(self.batch_size, 256), 1),
        )
        m = self.eval_fn(self.global_vars, x, y, mask)
        count = float(m["count"])
        return (
            float(m["loss_sum"]) / max(count, 1.0),
            float(m["correct"]) / max(count, 1.0),
        )

    def train(self, epochs: Optional[int] = None) -> dict:
        epochs = epochs if epochs is not None else self.config.fed.comm_round
        row = {}
        for e in range(epochs):
            row = self.train_epoch(e)
            if (e + 1) % self.config.fed.frequency_of_the_test == 0 or (
                e == epochs - 1
            ):
                loss, acc = self.evaluate()
                row.update({"Test/Loss": loss, "Test/Acc": acc})
            self.log_fn(row)
        return row
