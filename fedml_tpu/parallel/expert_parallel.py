"""Expert parallelism (EP): Mixture-of-Experts LM with the expert axis
sharded over an ``ep`` mesh axis.

The MoE layer itself (top-1 gate, dense dispatch, Switch aux loss) lives
with the other model components in models/transformer.py (``MoEMLP``,
activated via ``TransformerLM(moe_experts=E)`` — so MoE composes with any
attention core, including the sequence-parallel ones); this module adds
the sharding: expert weights placed P("ep", ...), so GSPMD turns the
final sum over experts into one all-reduce over ``ep`` and each device
holds and computes only its E/K experts — the expert-parallel layout with
compiler-derived collectives. Dense dispatch trades FLOPs for static
shapes; on TPU that is the right default at small expert counts (no
ragged all-to-all, no capacity overflow, MXU saturated); a
capacity-factor all_to_all dispatch is the known upgrade path at large E.

The reference has no MoE/EP (SURVEY §2g); first-class here per the task's
multi-chip contract.

This module is the mesh path. On the federated path (``FedAvgAPI``, the
``ModelDef`` contract of logits only) routed experts train through the
``decoder`` model (models/decoder.py): top-k routing over all experts,
grouped products over the sorted pairs of the experts a chip holds
(``experts_held``), no capacity and no dropped pair.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, PartitionSpec as P

from fedml_tpu.parallel.tensor_parallel import make_sharded_lm_train_step


def MoELM(vocab_size: int, num_experts: int = 4, embed_dim: int = 64, **kw):
    """TransformerLM configured as an MoE LM (returns (logits, aux))."""
    from fedml_tpu.models.transformer import TransformerLM

    return TransformerLM(
        vocab_size=vocab_size,
        moe_experts=num_experts,
        embed_dim=embed_dim,
        **kw,
    )


def ep_param_specs(params, ep_axis: str = "ep"):
    """Shard every MoE expert weight ([E, ...] leaves named w1/w2 under a
    ``moe`` scope) over ``ep_axis``; everything else replicated."""

    def spec(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        if any(n in ("w1", "w2") for n in names) and "moe" in names:
            return P(ep_axis)
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


def make_ep_train_step(
    mesh: Mesh,
    vocab_size: int,
    lr: float = 1e-3,
    ep_axis: str = "ep",
    dp_axis: Optional[str] = None,
    aux_coef: float = 0.01,
    **model_kw,
):
    """Build (init_fn, step_fn) for expert-parallel MoE-LM training.
    Same contract as tensor_parallel.make_tp_train_step."""
    model = MoELM(vocab_size, **model_kw)
    if model.moe_experts % mesh.shape[ep_axis]:
        raise ValueError(
            f"num_experts={model.moe_experts} not divisible by mesh axis "
            f"{ep_axis}={mesh.shape[ep_axis]}"
        )

    def loss_fn(model, p, tokens, targets):
        logits, aux = model.apply({"params": p}, tokens)
        ce = jnp.mean(
            optax.softmax_cross_entropy_with_integer_labels(logits, targets)
        )
        return ce + aux_coef * aux

    return make_sharded_lm_train_step(
        mesh,
        model,
        lambda params: ep_param_specs(params, ep_axis),
        loss_fn,
        lr=lr,
        dp_axis=dp_axis,
    )
