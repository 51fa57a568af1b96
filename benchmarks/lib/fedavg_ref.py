"""Plain FedAvg, the reference every cell's ``correct`` is decided against.

Straightforward ``jax.numpy`` in float32 at the highest matmul precision:
per client, ``epochs`` passes of masked minibatch SGD over the client's own
samples in the order the feed gives; then the sample-weighted average of the
clients' parameters. A step whose minibatch holds no real sample changes
nothing; a partly filled one averages over its real samples. It imports
nothing of the program and is given nothing the program made: weights and
data come from the seed through the configuration's own reference file.

The same code run with lower-precision ``Ops`` is the control (see
``tools/readings.py``)."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Ops:
    """How the reference multiplies. ``dtype`` is what parameters and
    activations are held and updated in; ``quant`` fake-quantizes both
    operands of every matmul to int8 (per-tensor absmax, straight-through
    gradient), which is how the bfloat16 cells' control computes."""

    dtype: str = "float32"
    quant: str = ""

    def _q(self, x):
        if not self.quant:
            return x
        if self.quant != "int8":
            raise ValueError(f"unknown quantization {self.quant!r}")
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30).astype(jnp.float32) / 127.0
        q = (jnp.round(x / scale) * scale).astype(x.dtype)
        return x + jax.lax.stop_gradient(q - x)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self._q(a), self._q(b))

    def dot(self, a, w):
        return jnp.dot(self._q(a), self._q(w))

    def conv(self, x, w):
        return jax.lax.conv_general_dilated(
            self._q(x), self._q(w), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )


REFERENCE = Ops()


def task_loss(task, logits, y, mask):
    """(mean loss over real units, correct units, real units). A unit is a
    sample (classification) or a non-pad token (nwp, pad id 0)."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    hit = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
    if task == "classification":
        w = mask
    elif task == "nwp":
        w = (y != 0).astype(jnp.float32) * mask[:, None]
    else:
        raise ValueError(f"the reference has no loss for task {task!r}")
    total = jnp.sum(w)
    return jnp.sum(nll * w) / jnp.maximum(total, 1e-9), jnp.sum(hit * w), total


def make_local_train(logits_fn, task, lr, epochs, ops):
    """``(params, x [E,S,B,..], y, mask [E,S,B]) -> (params', loss_sum,
    units)``: plain SGD on the mean loss of each minibatch's real units, one
    client."""

    def loss_sum_fn(params, xb, yb, mb):
        mean, correct, total = task_loss(task, logits_fn(params, xb, ops), yb, mb)
        return mean * total, total

    grad_fn = jax.value_and_grad(loss_sum_fn, has_aux=True)

    def step(params, batch):
        (ls, tot), g = grad_fn(params, *batch)
        has = tot > 0
        inv = 1.0 / jnp.maximum(tot, 1e-9)
        new = jax.tree_util.tree_map(
            lambda p, gg: jnp.where(has, p - (lr * inv * gg).astype(p.dtype), p),
            params, g,
        )
        return new, (ls, tot)

    def local_train(params, x, y, mask):
        def epoch(p, ep):
            p, (ls, tot) = jax.lax.scan(step, p, ep)
            return p, (jnp.sum(ls), jnp.sum(tot))

        if epochs != x.shape[0]:
            raise ValueError("the feed's epochs and the cell's differ")
        params, (ls, tot) = jax.lax.scan(epoch, params, (x, y, mask))
        return params, jnp.sum(ls), jnp.sum(tot)

    return local_train


def make_eval(logits_fn, task, ops):
    def eval_block(params, xb, yb, mb):
        mean, correct, total = task_loss(task, logits_fn(params, xb, ops), yb, mb)
        return mean * total, correct, total

    return jax.jit(eval_block)


def leaf_norms(new, old):
    """Per-leaf L2 norm of ``new - old`` in float32, as a dict of floats."""
    out = {}
    for k in new:
        d = new[k].astype(jnp.float32) - old[k].astype(jnp.float32)
        out[k] = float(jnp.sqrt(jnp.sum(d * d)))
    return out


def follow(ref, model_cfg, cell, feed, seed, rounds, ops=REFERENCE, client_block=32, fault=""):
    """Follow the first ``rounds`` federated rounds from the seed's weights.

    Returns the per-round training loss, the test loss and accuracy after
    each followed round that the cell evaluates, and the per-leaf norms of
    the parameters' change after round 1 and after the last round.

    ``fault="half_batch"`` plants a fault for the limits' upper readings:
    the second half of every minibatch is left out and the mean taken over
    the rest."""
    if fault not in ("", "half_batch"):
        raise ValueError(f"unknown fault {fault!r}")
    task = model_cfg["task"]
    dtype = jnp.dtype(ops.dtype)
    logits_fn = functools.partial(ref.logits_fn, cfg=model_cfg)
    local = make_local_train(logits_fn, task, cell["lr"], cell["epochs"], ops)
    block_train = jax.jit(
        lambda p, x, y, m: jax.lax.map(lambda c: local(p, *c), (x, y, m))
    )
    evaluate = make_eval(logits_fn, task, ops)

    with jax.default_matmul_precision("highest"):
        params0 = ref.init_params(seed, model_cfg)
        params = {k: v.astype(dtype) for k, v in params0.items()}
        out = {"loss": [], "eval": {}, "units": []}
        for r in range(rounds):
            plan = feed.round_plan(r)
            acc = {k: jnp.zeros(v.shape, jnp.float32) for k, v in params.items()}
            ls = tot = 0.0
            C = len(plan.clients)
            for lo in range(0, C, client_block):
                x, y, m = feed.client_batches(plan, lo, min(C, lo + client_block))
                if fault == "half_batch":
                    m = m.at[..., m.shape[-1] // 2:].set(0.0)
                if jnp.issubdtype(x.dtype, jnp.floating):
                    x = x.astype(dtype)
                newp, l, t = block_train(params, x, y, m)
                w = jnp.asarray(plan.sizes[lo:lo + client_block], jnp.float32)
                for k in acc:
                    acc[k] = acc[k] + jnp.tensordot(
                        w, newp[k].astype(jnp.float32), axes=1
                    )
                ls += float(jnp.sum(l))
                tot += float(jnp.sum(t))
            wsum = float(np.sum(plan.sizes))
            params = {k: (v / wsum).astype(dtype) for k, v in acc.items()}
            out["loss"].append(ls / max(tot, 1e-9))
            out["units"].append(tot)
            if r == 0:
                out["norms_first"] = leaf_norms(params, params0)
            if feed.is_eval_round(r, rounds):
                els = ecorrect = etot = 0.0
                for xb, yb, mb in feed.eval_batches():
                    if jnp.issubdtype(xb.dtype, jnp.floating):
                        xb = xb.astype(dtype)
                    a, b, c = evaluate(params, xb, yb, mb)
                    els, ecorrect, etot = els + float(a), ecorrect + float(b), etot + float(c)
                out["eval"][r] = (els / max(etot, 1e-9), ecorrect / max(etot, 1e-9))
        out["norms_last"] = leaf_norms(params, params0)
    return out
