"""Sequence-parallel (SP) causal-LM training — the long-context training
path: the sequence axis of every activation lives on a mesh axis; attention
is ring attention over ICI; the loss is a psum-mean.

Composable with FL: a 2-D Mesh ("clients", "seq") runs FL clients as one
axis and splits each client's long sequences over the other — the layout
SURVEY §2h calls for (collectives ride ICI). This module provides the 1-D
"seq" step used by the flagship long-context trainer and the dryrun."""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.models.transformer import TransformerLM
from fedml_tpu.parallel.ring_attention import ring_attention_sharded
from fedml_tpu.parallel.ulysses import ulysses_attention_sharded


def make_sp_lm(
    vocab_size: int,
    axis_name: str = "seq",
    sp_impl: str = "ring",
    local_attn_fn=None,
    **model_kw,
) -> TransformerLM:
    """TransformerLM wired with sequence-parallel attention over
    ``axis_name`` (must be called inside shard_map). ``sp_impl``: "ring"
    (K/V rotation, ring_attention.py) or "ulysses" (all-to-all head
    re-sharding, ulysses.py; needs num_heads % axis_size == 0).
    ``local_attn_fn`` (ulysses only) replaces the per-device attention core
    on the gathered [B, T, H_local, D] blocks — e.g. a flash-backed callable
    so long sequences never materialise T×T scores."""
    if sp_impl == "ring":
        if local_attn_fn is not None:
            raise ValueError("local_attn_fn is only meaningful for ulysses")
        attn = functools.partial(
            ring_attention_sharded, axis_name=axis_name, causal=True
        )
    elif sp_impl == "ulysses":
        attn = functools.partial(
            ulysses_attention_sharded,
            axis_name=axis_name,
            causal=True,
            attn_fn=local_attn_fn,
        )
    else:
        raise ValueError(f"unknown sp_impl {sp_impl!r} (ring|ulysses)")
    if model_kw.get("moe_experts"):
        # exact global Switch aux under the seq sharding (MoEMLP pmeans the
        # routing stats over this axis before forming the product)
        model_kw.setdefault("moe_stats_axis", axis_name)
    return TransformerLM(vocab_size=vocab_size, attn_fn=attn, **model_kw)


def make_sp_train_step(
    mesh: Mesh,
    vocab_size: int,
    lr: float = 1e-3,
    axis_name: str = "seq",
    sp_impl: str = "ring",
    local_attn_fn=None,
    aux_coef: float = 0.01,
    **model_kw,
):
    """Build (init_fn, step_fn) for sequence-parallel LM training.

    step_fn(params, opt_state, tokens, targets) with tokens/targets
    [B, T] sharded on T over the mesh; params replicated. The loss mean and
    grads are psum'd over the ring — one SPMD program, no host round-trips.
    Pass ``moe_experts=E`` to run MoE blocks under SP (expert weights
    replicated here; shard them over a second mesh axis for true EP×SP).
    ``aux_coef`` weighs the Switch load-balance loss, same knob as
    expert_parallel.make_ep_train_step.
    """
    if sp_impl == "ulysses":
        heads = model_kw.get("num_heads", TransformerLM.num_heads)
        n = mesh.shape[axis_name]
        if heads % n:
            raise ValueError(
                f"ulysses needs num_heads % mesh axis size == 0; got "
                f"num_heads={heads}, {axis_name}={n}"
            )
    model = make_sp_lm(
        vocab_size, axis_name, sp_impl=sp_impl, local_attn_fn=local_attn_fn,
        **model_kw,
    )
    opt = optax.adamw(lr)

    def shard_body(params, opt_state, tokens, targets):
        T_local = tokens.shape[1]
        offset = jax.lax.axis_index(axis_name) * T_local

        def loss_fn(p):
            out = model.apply({"params": p}, tokens, pos_offset=offset)
            if model.moe_experts:
                # (logits, aux): aux is already the exact GLOBAL Switch
                # load-balance loss (MoEMLP pmeans the routing stats over
                # the seq axis), identical on every shard — no reduction
                logits, aux = out
            else:
                logits, aux = out, 0.0
            per_tok = optax.softmax_cross_entropy_with_integer_labels(
                logits, targets
            )
            # global mean over the full sequence
            s = jax.lax.psum(jnp.sum(per_tok), axis_name)
            n = jax.lax.psum(per_tok.size, axis_name)
            return s / n + aux_coef * aux

        # Differentiate at the VARYING view of the replicated (P()) params:
        # each shard's grads are then typed {V:seq} shard-local partial
        # sums — which is what the custom-VJP norm ops (ops/fused_*.py)
        # return for a replicated parameter, and what jax's
        # varying-manual-axes check would otherwise reject — and the
        # explicit psum below is the one reduction, making the outputs
        # provably replicated. Pinned bit-exact vs single-device training
        # in tests/test_ring_attention.py::test_sp_lm_matches_single_device.
        loss, grads = jax.value_and_grad(loss_fn)(
            jax.lax.pcast(params, axis_name, to="varying")
        )
        grads = jax.lax.psum(grads, axis_name)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    data_spec = P(None, axis_name)
    step = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(), P(), data_spec, data_spec),
        out_specs=(P(), P(), P()),
    )

    def init_fn(rng, example_tokens):
        # init runs OUTSIDE shard_map — stats_axis (a pmean axis) must be
        # unset here; param structure doesn't depend on it
        model_full = TransformerLM(
            vocab_size=vocab_size, **{**model_kw, "moe_stats_axis": None}
        )
        variables = model_full.init({"params": rng}, example_tokens[:, :8])
        params = variables["params"]
        return params, opt.init(params)

    return init_fn, jax.jit(step)  # fedlint: disable=uncached-jit -- bespoke long-context training step closed over mesh/opt; built once per benchmark run
