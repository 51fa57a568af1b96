"""Decoder-only transformer LM with pluggable attention — the long-context
flagship (green-field vs the reference, whose only NLP models are 2-layer
LSTMs, fedml_api/model/nlp/rnn.py; SURVEY §5 marks sequence parallelism
absent).

The attention callable is injected so the SAME module runs single-chip
(causal attention through ``ops/attention.py``) or sequence-parallel (ring attention inside
shard_map — parallel/long_context.py). Pre-LN blocks, learned positional
embeddings indexed by GLOBAL position (the seq-sharded path passes each
shard's offset), GELU MLP. bfloat16-friendly: all matmuls keep bf16 inputs
with fp32 softmax accumulation in the attention implementations."""

from __future__ import annotations

import functools
from typing import Callable, Optional

import flax.linen as nn

from fedml_tpu.models.norms import fp32_layer_norm
import jax
import jax.numpy as jnp

from fedml_tpu.ops.attention import attention, takes_kernel

causal_attention = functools.partial(attention, causal=True)


class MoEMLP(nn.Module):
    """Top-1 routed Mixture-of-Experts MLP: x [B, T, C] → (y [B, T, C],
    aux) where aux is the Switch-Transformer load-balancing loss
    (mean fraction-of-tokens × mean gate prob × E). Dense dispatch — every
    expert computes every token, the top-1 mask selects — trades FLOPs for
    static shapes; sharded P("ep", ...) over a mesh (parallel/
    expert_parallel.py) the sum over experts becomes one all-reduce."""

    num_experts: int
    mlp_ratio: int = 4
    # When tokens are sharded over a mesh axis (sequence parallelism), the
    # Switch aux is a product of token-means — averaging per-shard finished
    # products is biased by the cross-shard covariance. Setting stats_axis
    # pmeans frac/mean_prob BEFORE the product, so aux is the exact global
    # load-balance loss (identical on every shard).
    stats_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        E, F = self.num_experts, self.mlp_ratio * C
        gate_logits = nn.Dense(E, use_bias=False, name="gate")(x)  # [B,T,E]
        probs = jax.nn.softmax(gate_logits, axis=-1)
        top1 = jnp.argmax(probs, axis=-1)  # [B,T]
        mask = jax.nn.one_hot(top1, E, dtype=x.dtype)
        frac = jnp.mean(mask, axis=(0, 1))
        mean_prob = jnp.mean(probs, axis=(0, 1))
        if self.stats_axis is not None:
            frac, mean_prob = jax.lax.pmean(
                (frac, mean_prob), self.stats_axis
            )
        aux = E * jnp.sum(frac * mean_prob)

        w1 = self.param("w1", nn.initializers.lecun_normal(), (E, C, F))
        w2 = self.param("w2", nn.initializers.lecun_normal(), (E, F, C))
        h = jnp.einsum("btc,ecf->ebtf", x, w1)
        h = nn.gelu(h)
        y_e = jnp.einsum("ebtf,efc->ebtc", h, w2)
        sel = mask * jnp.take_along_axis(probs, top1[..., None], axis=-1)
        y = jnp.einsum("ebtc,bte->btc", y_e, sel)
        return y, aux


class TransformerBlock(nn.Module):
    """Pre-LN block. ``moe_experts > 0`` swaps the dense MLP for MoEMLP, in
    which case __call__ returns (x, aux) instead of x."""

    num_heads: int
    mlp_ratio: int = 4
    attn_fn: Callable = causal_attention
    moe_experts: int = 0
    moe_stats_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, train: bool = False):
        B, T, C = x.shape
        H = self.num_heads
        D = C // H
        h = fp32_layer_norm(name="ln1")(x)
        qkv = nn.Dense(3 * C, use_bias=False, name="qkv")(h)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, T, H, D)
        k = k.reshape(B, T, H, D)
        v = v.reshape(B, T, H, D)
        # attn_fn is a plain function, not a module: without a scope its ops
        # sit directly under the block, beside the residual adds and the GELU
        with jax.named_scope("attention"):
            attn = self.attn_fn(q, k, v)
        attn = attn.reshape(B, T, C)
        x = x + nn.Dense(C, use_bias=False, name="proj")(attn)
        h = fp32_layer_norm(name="ln2")(x)
        if self.moe_experts:
            y, aux = MoEMLP(
                self.moe_experts, self.mlp_ratio,
                stats_axis=self.moe_stats_axis, name="moe",
            )(h)
            return x + y, aux
        h = nn.Dense(self.mlp_ratio * C, name="mlp_up")(h)
        h = nn.gelu(h)
        return x + nn.Dense(C, name="mlp_down")(h)


class TransformerLM(nn.Module):
    """``moe_experts > 0`` swaps every block's dense MLP for MoEMLP and
    makes __call__ return (logits, mean aux loss) — MoE composes with any
    attn_fn, including the sequence-parallel ring/ulysses cores."""

    vocab_size: int
    num_layers: int = 2
    num_heads: int = 4
    embed_dim: int = 128
    max_len: int = 4096
    attn_fn: Callable = causal_attention
    moe_experts: int = 0
    moe_stats_axis: Optional[str] = None

    def flush_attrs(self, length: int, batch: int) -> dict:
        """``ModelDef.flush_attrs``: one attention site a layer where the
        blocks' attention is ``ops/attention.attention``, and how many of
        them take its kernel at ``length``."""
        if self.attn_fn is not causal_attention:
            return {}
        site = (self.num_heads, self.num_heads, self.embed_dim // self.num_heads)
        return {"attn_kernel_sites": self.num_layers * takes_kernel(length, *site),
                "attn_sites": self.num_layers}

    @nn.compact
    def __call__(self, tokens, pos_offset: int = 0, train: bool = False):
        """tokens [B, T_local]; pos_offset = this shard's global start."""
        B, T = tokens.shape
        tok = nn.Embed(self.vocab_size, self.embed_dim, name="tok_embed")(tokens)
        pos_table = self.param(
            "pos_embed",
            nn.initializers.normal(0.02),
            (self.max_len, self.embed_dim),
        )
        pos = jnp.arange(T) + pos_offset
        x = tok + pos_table[pos]
        aux_total = 0.0
        for i in range(self.num_layers):
            block = TransformerBlock(
                self.num_heads,
                attn_fn=self.attn_fn,
                moe_experts=self.moe_experts,
                moe_stats_axis=self.moe_stats_axis,
                name=f"block{i}",
            )
            if self.moe_experts:
                x, aux = block(x, train=train)
                aux_total = aux_total + aux
            else:
                x = block(x, train=train)
        x = fp32_layer_norm(name="ln_f")(x)
        logits = nn.Dense(self.vocab_size, use_bias=False, name="head")(x)
        if self.moe_experts:
            return logits, aux_total / self.num_layers
        return logits
