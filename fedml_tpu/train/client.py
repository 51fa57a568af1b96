"""Local (client) training operator.

Replaces the reference's ModelTrainer ABC + per-task trainers
(fedml_core/trainer/model_trainer.py:41-81;
fedml_api/standalone/fedavg/my_model_trainer_classification.py:19-54) with one
pure function: ``local_train(variables, x, y, mask, rng) -> (variables',
metrics)`` — a `lax.scan` of optimizer steps over [epochs × steps] minibatches.
It is vmap-able over a client axis (the standalone simulator) and shard_map-able
over a device mesh (the distributed runtime); the reference's epoch×batch torch
loop is HOT LOOP #2 of SURVEY §3.1.

The FedProx proximal term μ/2·‖w − w_global‖² is included when
``train_config.prox_mu > 0`` — present in the reference only in FedNova's
optimizer (standalone/fednova/fednova.py:120s); its distributed fedprox omits
it (SURVEY §2b row fedprox)."""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax

from fedml_tpu.config import TrainConfig
from fedml_tpu.models import ModelDef
from fedml_tpu.train import losses as L


def build_client_optimizer(tc: TrainConfig) -> optax.GradientTransformation:
    """torch-semantics optimizers (ref my_model_trainer_classification.py
    get_optimizer: SGD(lr) | Adam(lr, wd, amsgrad=True)). Weight decay is
    L2-added-to-grad (torch style), not decoupled."""
    parts = []
    if tc.wd:
        parts.append(optax.add_decayed_weights(tc.wd))
    if tc.client_optimizer == "sgd":
        parts.append(optax.sgd(tc.lr, momentum=tc.momentum if tc.momentum else None))
    elif tc.client_optimizer == "adam":
        parts.append(optax.amsgrad(tc.lr))
    else:
        raise ValueError(f"unknown client_optimizer {tc.client_optimizer!r}")
    return optax.chain(*parts)


def _split_vars(variables: dict) -> Tuple[dict, dict]:
    params = variables["params"]
    extra = {k: v for k, v in variables.items() if k != "params"}
    return params, extra


def cast_floats(tree, dtype):
    """Cast every floating leaf; ints (labels, step counts) pass through."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
        else a,
        tree,
    )


def make_mixed_forward(model: ModelDef, tc: TrainConfig, counters: bool = False):
    """The shared mixed-precision forward: fp32 master params are cast to
    ``tc.compute_dtype`` inside the differentiated function (the cast is
    linear, so grads come back fp32); logits are restored to fp32 so scan
    carries keep stable dtypes. Mutable collections (BN running stats) are
    NEVER cast down: batch statistics are fp32-only territory — the zoo's
    BatchNorms normalize in fp32 and cast back (models/norms.py), and
    quantizing the running-stat EMA to bf16 each step would re-inject the
    error that helper exists to remove. When
    ``tc.augment`` names a policy (train/augment.py), per-sample
    augmentation runs here — inside jit, fused with the forward — so both
    the federated and centralized paths share one definition.

    Returns ``fwd(params, extra, xb, step_rng) -> (logits_f32, new_extra_f32)``;
    with ``counters=True`` a third item, the model's device counters of this
    call as one float32 vector (``ModelDef.counters`` names its entries).
    Used by both the per-client local-train scan and the centralized DP
    trainer so the compute-dtype policy can never diverge between them."""
    from fedml_tpu.train.augment import resolve_augment

    cdt = jnp.dtype(tc.compute_dtype)
    mixed = cdt != jnp.dtype(jnp.float32)
    augment_fn = resolve_augment(getattr(tc, "augment", "none"))
    # asked for only where wanted: a model object without the keyword still applies
    apply_kw = {"counters": True} if counters else {}

    def fwd(params, extra, xb, step_rng):
        if augment_fn is not None:
            if step_rng is None:
                # a silent PRNGKey(0) fallback would freeze one augmentation
                # pattern for the whole run — fail loudly instead
                raise ValueError("augmentation requires a step rng")
            xb = augment_fn(jax.random.fold_in(step_rng, 7), xb)
        if mixed:
            params_c = cast_floats(params, cdt)
            xb_c = cast_floats(xb, cdt)
        else:
            params_c, xb_c = params, xb
        logits, new_vars, *counted = model.apply(
            {"params": params_c, **extra}, xb_c, train=True, rng=step_rng, **apply_kw
        )
        logits = logits.astype(jnp.float32)
        if mixed:
            new_vars = cast_floats(new_vars, jnp.float32)
        _, new_extra = _split_vars(new_vars)
        return (logits, new_extra, *counted)

    return fwd


def make_task_loss(task: str) -> Callable:
    """task → (loss, (correct, total)) (ref per-task MyModelTrainer impls)."""

    def classification(logits, y, mask):
        loss = L.masked_softmax_ce(logits, y, mask)
        correct, total = L.masked_accuracy_stats(logits, y, mask)
        return loss, correct, total

    def nwp(logits, y, mask):
        loss = L.masked_seq_ce(logits, y, mask)
        correct, total = L.masked_seq_accuracy_stats(logits, y, mask)
        return loss, correct, total

    def tag(logits, y, mask):
        loss = L.masked_sigmoid_bce(logits, y, mask)
        pred = (logits > 0).astype(jnp.float32)
        correct = jnp.sum((pred == y).astype(jnp.float32) * mask[:, None])
        total = jnp.sum(mask) * y.shape[-1]
        return loss, correct, total

    def segmentation(logits, y, mask):
        loss = L.masked_pixel_ce(logits, y, mask)
        correct, total = L.masked_pixel_accuracy_stats(logits, y, mask)
        return loss, correct, total

    return {
        "classification": classification,
        "nwp": nwp,
        "tag": tag,
        "segmentation": segmentation,
    }[task]


def masked_epoch_perm(ep_rng, m_flat):
    """Mask-aware shuffle permutation — THE shared shuffle contract (used
    by make_local_train and the SCAFFOLD local train; a divergence here
    would silently change which samples share a minibatch): draw a key per
    slot, pin padded slots to +inf, argsort. Valid samples (slots 0..n-1
    by the stacking contract) get a random order in the first ceil(n/bs)
    minibatches; padding compacts to trailing all-padding steps. Because
    uniform draws are per-position (threefry partitionable) and valid
    slots always occupy the prefix, minibatch composition is INDEPENDENT
    of the padded capacity."""
    keys = jnp.where(
        m_flat > 0, jax.random.uniform(ep_rng, m_flat.shape), jnp.inf
    )
    return jnp.argsort(keys)


def make_local_train(
    model: ModelDef,
    tc: TrainConfig,
    epochs: int,
    task: str = "classification",
    reshuffle_each_epoch: bool = True,
    skip_empty_steps: bool = False,
    external_prox: bool = False,
):
    """Build the per-client training function.

    Returned fn: ``(variables, x, y, mask, rng) -> (variables', metrics)`` with
    x [S, B, *feat], y [S, B, *lab], mask [S, B]. metrics are SUMS
    {loss_sum, correct, count} so they aggregate exactly across clients; a
    model that reports device counters (``ModelDef.counters``) adds one sum
    per counter under its name, over the real steps, and a model that
    reports none leaves the metrics as they are.

    ``external_prox=True`` prepends a parameter tree to the signature —
    ``(prox_ref_params, variables, x, y, mask, rng)`` — and points the
    tc.prox_mu proximal term at it instead of the entry params. FedProx
    pulls toward the entry params (which ARE the broadcast global model);
    Ditto's personal step starts from the personal model but pulls toward
    the broadcast global model, so the reference must be external
    (algorithms/ditto.py). One loop serves both, keeping their math
    bit-identical at prox_mu=0 by construction.
    """
    opt = build_client_optimizer(tc)
    task_loss = make_task_loss(task)
    counter_names = tuple(getattr(model, "counters", ()))
    fwd = make_mixed_forward(model, tc, counters=bool(counter_names))

    def _local_train(variables, x, y, mask, rng, prox_ref=None):
        params0, extra0 = _split_vars(variables)
        prox_ref_params = params0 if prox_ref is None else prox_ref
        S, B = mask.shape[0], mask.shape[1]
        n_flat = S * B
        x_flat = x.reshape((n_flat,) + x.shape[2:])
        y_flat = y.reshape((n_flat,) + y.shape[2:])
        m_flat = mask.reshape((n_flat,))

        def loss_fn(params, extra, xb, yb, mb, step_rng):
            logits, new_extra, *counted = fwd(params, extra, xb, step_rng)
            task_l, correct, total = task_loss(logits, yb, mb)
            loss = task_l
            if tc.prox_mu:
                loss = loss + 0.5 * tc.prox_mu * L.tree_sq_dist(
                    params, prox_ref_params
                )
            # task_l (not loss) feeds the metrics so FedProx runs report plain
            # task loss, comparable to FedAvg and the reference's logs.
            return loss, (new_extra, task_l, correct, total, *counted)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        def epoch_body(carry, epoch_idx):
            params, extra, opt_state = carry
            ep_rng = jax.random.fold_in(rng, epoch_idx)
            if reshuffle_each_epoch:
                # masked_epoch_perm: minibatch composition does not depend
                # on the padded capacity — see its docstring
                perm = masked_epoch_perm(ep_rng, m_flat)
            else:
                perm = jnp.arange(n_flat)
            xe = x_flat[perm].reshape(x.shape)
            ye = y_flat[perm].reshape(y.shape)
            me = m_flat[perm].reshape(mask.shape)

            def step_body(carry, inp):
                xb, yb, mb, sidx = inp
                # An all-padding step (mask sum 0) must be a complete no-op:
                # masked-mean grads are already 0, but momentum/Adam state and
                # the prox term would still move params — and the compute
                # itself is pure padding waste.
                has_data = jnp.sum(mb) > 0

                def real_step(carry):
                    params, extra, opt_state = carry
                    step_rng = jax.random.fold_in(ep_rng, sidx)
                    with jax.named_scope("forward_backward"):
                        (_, (new_extra, task_l, correct, total, *counted)), grads = grad_fn(
                            params, extra, xb, yb, mb, step_rng
                        )
                    with jax.named_scope("optimizer_update"):
                        updates, new_opt_state = opt.update(
                            grads, opt_state, params
                        )
                        new_params = optax.apply_updates(params, updates)
                    mets = jnp.stack(
                        [task_l * total, correct, total, jnp.float32(1)]
                    )
                    if counted:
                        mets = jnp.concatenate([mets, counted[0]])
                    return (new_params, new_extra, new_opt_state), mets

                if skip_empty_steps:
                    # Real skipped branch: the predicate is a scalar in the
                    # sequential ("scan") client schedule, so lax.cond
                    # genuinely skips the fwd/bwd — padded steps cost
                    # ~nothing, which is what lets fused round chunks pad
                    # every round to a shared step count for free.
                    def skip_step(carry):
                        return carry, jnp.zeros((4 + len(counter_names),), jnp.float32)

                    return jax.lax.cond(has_data, real_step, skip_step, carry)

                # Batched schedules (vmap clients, shard_map mesh): the
                # predicate is per-client, a branch is impossible — compute
                # and where-gate every carry leaf instead.
                (new_params, new_extra, new_opt_state), mets = real_step(carry)
                params, extra, opt_state = carry

                def keep(new, old):
                    return jax.tree_util.tree_map(
                        lambda n, o: jnp.where(has_data, n, o), new, old
                    )

                with jax.named_scope("keep_gate"):
                    return (
                        keep(new_params, params),
                        keep(new_extra, extra),
                        keep(new_opt_state, opt_state),
                    ), mets * has_data.astype(jnp.float32)

            (params, extra, opt_state), mets = jax.lax.scan(
                step_body,
                (params, extra, opt_state),
                (xe, ye, me, jnp.arange(S)),
            )
            return (params, extra, opt_state), mets.sum(axis=0)

        opt_state = opt.init(params0)
        (params, extra, _), mets = jax.lax.scan(
            epoch_body, (params0, extra0, opt_state), jnp.arange(epochs)
        )
        mets = mets.sum(axis=0)
        # "steps" = effective local optimizer steps (all-padding steps are
        # gated no-ops and not counted) — FedNova's τ_i normalizer.
        metrics = {
            "loss_sum": mets[0],
            "correct": mets[1],
            "count": mets[2],
            "steps": mets[3],
        }
        metrics.update({name: mets[4 + i] for i, name in enumerate(counter_names)})
        return {"params": params, **extra}, metrics

    if external_prox:
        def local_train(prox_ref_params, variables, x, y, mask, rng):
            return _local_train(variables, x, y, mask, rng, prox_ref=prox_ref_params)
    else:
        def local_train(variables, x, y, mask, rng):
            return _local_train(variables, x, y, mask, rng)

    return local_train
