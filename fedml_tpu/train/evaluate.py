"""Jit-compiled evaluation (ref: server-side test,
FedAVGAggregator.py:100-157 / my_model_trainer_classification.py:56-86)."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.models import ModelDef
from fedml_tpu.train.client import make_task_loss


def pad_to_batches(x: np.ndarray, y: np.ndarray, batch_size: int):
    """Host-side: pad test arrays to a whole number of batches + mask."""
    n = x.shape[0]
    steps = (n + batch_size - 1) // batch_size
    cap = steps * batch_size
    xp = np.zeros((cap,) + x.shape[1:], dtype=x.dtype)
    yp = np.zeros((cap,) + y.shape[1:], dtype=y.dtype)
    mp = np.zeros((cap,), dtype=np.float32)
    xp[:n], yp[:n], mp[:n] = x, y, 1.0
    return (
        xp.reshape((steps, batch_size) + x.shape[1:]),
        yp.reshape((steps, batch_size) + y.shape[1:]),
        mp.reshape((steps, batch_size)),
    )


def make_eval_fn(model: ModelDef, task: str = "classification"):
    """Returns jitted ``eval_fn(variables, x, y, mask) -> {loss_sum, correct,
    count}`` over batched inputs x [S, B, ...].

    Deduped through the process-wide ProgramCache (fedml_tpu/compile/):
    every API instance over the same (model, task) shares ONE jitted eval
    program instead of recompiling per constructor call."""
    task_loss = make_task_loss(task)

    def builder():
        @jax.jit
        def eval_fn(variables, x, y, mask):
            def body(carry, inp):
                xb, yb, mb = inp
                logits, _ = model.apply(variables, xb, train=False)
                loss, correct, total = task_loss(logits, yb, mb)
                return carry + jnp.stack([loss * total, correct, total]), None

            with jax.named_scope("eval"):
                sums, _ = jax.lax.scan(body, jnp.zeros(3), (x, y, mask))
            return {"loss_sum": sums[0], "correct": sums[1], "count": sums[2]}

        return eval_fn

    from fedml_tpu.compile import get_program_cache, model_fingerprint

    return get_program_cache().get_or_build(
        "eval",
        {
            "kind": "eval",
            "model": model_fingerprint(model),
            "task": task,
        },
        builder,
    )


def metrics_to_loss_acc(m) -> Tuple[float, float]:
    """{loss_sum, correct, count} sums → (mean loss, accuracy). The one
    derivation shared by every eval surface."""
    count = float(m["count"])
    return (
        float(m["loss_sum"]) / max(count, 1e-9),
        float(m["correct"]) / max(count, 1e-9),
    )


def evaluate(model: ModelDef, variables, x, y, batch_size: int = 256, task="classification", eval_fn=None):
    """Convenience host wrapper: returns (loss, accuracy)."""
    xb, yb, mb = pad_to_batches(np.asarray(x), np.asarray(y), batch_size)
    fn = eval_fn or make_eval_fn(model, task)
    return metrics_to_loss_acc(fn(variables, xb, yb, mb))
