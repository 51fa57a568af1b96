"""Plain reference of the LFM2-8B-A1B decoder (Liquid AI; Hugging Face
``LiquidAI/LFM2-8B-A1B``, ``config.json``, ``model_type`` ``lfm2_moe``), given
this chip's share of the stated deployment: experts ``experts_held`` of every
expert layer and the first ``vocab_size`` rows of the vocabulary. The
equations are HF's ``Lfm2Moe`` with this config (``norm_eps`` 1e-5):

    x0 = E[token]
    h  = x + Mixer_i(RMSNorm_operator(x))          y = h + FFN_i(RMSNorm_ffn(h))
    logits = RMSNorm_final(y_L) W_head                         untied head

``layer_types[i] == "conv"``, the gated short convolution, n = RMSNorm(x):
    [B, C, X] = n W_in            2048 -> 3 x 2048, split in that order
    u   = B * X
    c_t = sum_{j < L} w[:, j] * u_{t-(L-1)+j}      u zero before position 0
    Mixer = (C * c) W_out
  a causal depthwise convolution, one filter of ``conv_L_cache`` = 3 taps a
  channel, no bias; written below as the explicit sum over the L shifted
  copies of ``u``. No activation, no softmax, no positions.

``"full_attention"``: 32 query heads on 8 key/value heads of 64;
    q = n Wq, k = n Wk, v = n Wv
    q <- RMSNorm_64(q), k <- RMSNorm_64(k)   over each head's dims, one learned
                                             scale for all heads of q, one for k
    rotate-half rotary on all 64 dims of q and k, theta 1e6
    s_ij = q_i . k_j / 8, mask j <= i;  a = softmax(s) v;  Mixer = a Wo
  The T x T scores are written out per key/value head (its 4 query heads at
  a time, 8 times): the same products, so that the backward pass's float32
  temporaries are an eighth of all 32 heads' at once. At T = 4096 the kept
  probabilities are 2.1 GB and would be three times that in flight.

FFN: the first ``num_dense_layers`` layers (``model.kwargs`` spells the
source's keys as the decoder does: ``first_k_dense_replace``,
``rms_norm_eps``, ``topk_method`` ``noaux_tc`` for ``use_expert_bias``,
``renorm_eps`` for the router's 1e-6) a gated-SiLU MLP of 7168; every
other layer routed experts (HF's ``Lfm2MoeSparseMoeBlock``): ``s = sigmoid(m
Wr)`` over all 32 in float32; the top 4 of ``s + b`` are chosen
(``use_expert_bias``: ``b``, HF's ``expert_bias`` buffer, enters the choice
and not the weight); ``w = s[chosen] / (sum s[chosen] + 1e-6)``
(``norm_topk_prob``) times ``routed_scaling_factor`` 1; ``Routed(m) = sum
over the chosen slots whose expert is held here of w_slot * Wdown_e
(silu(Wgate_e m) * Wup_e m)``, width 1792. No shared expert, no capacity, no
dropped pair; what the absent experts would add is left out (model-configs
guide, section 4).

The three products of every (token, slot) pair run as grouped products
(``jax.lax.ragged_dot``) over the pairs sorted by expert, the pairs of absent
experts last and outside every group, and not as every held expert on every
token under a mask: the benchmark counts the FLOPs that ``round.mfu_pct``
divides by the peak in the jaxpr of THIS file's loss, and the masked form
would count 8 experts a token where routing requires 4 x 8 / 32. The counter
skips ``ragged_dot``, so ``round.mfu_pct`` leaves the routed products out and
``moe.expert_peak_pct`` holds them. Every other product is ``ops.dot`` or
``ops.einsum``, which the int8 control quantises; the convolution has no
product (it is elementwise) and attention is counted full T x T as written.

Departures from the source, each also under ``assumed`` in the configuration
file: the selection bias is a parameter leaf (HF: a buffer) that nothing
updates (its gradient is zero: it only picks indices); the head is untied;
no auxiliary loss; ``u``, the convolution's sums and the rotary operands are
float32 (all of this file is)."""

import functools
import math

import jax
import jax.numpy as jnp

# Scale of the selection bias drawn from the seed (see ``init_params``).
BIAS_SCALE = 0.015


def _spec(cfg):
    m = cfg["model"]
    kw = dict(m["kwargs"])
    kw["vocab_size"] = int(m["num_classes"])
    kw["length"] = int(m["input_shape"][0])
    kw["held"] = tuple(kw.get("experts_held") or (0, kw["num_experts"]))
    return kw


def param_shapes(cfg):
    s = _spec(cfg)
    V, d, H, KV = s["vocab_size"], s["hidden_size"], s["num_attention_heads"], s["num_key_value_heads"]
    D, L, E, f = s["head_dim"], s["conv_L_cache"], s["num_experts"], s["moe_intermediate_size"]
    Eh = s["held"][1] - s["held"][0]
    shapes = {"embed_tokens/embedding": (V, d), "norm/scale": (d,), "lm_head/kernel": (d, V)}
    for i, kind in enumerate(s["layer_types"]):
        b = f"layers_{i}/"
        shapes.update({b + "input_layernorm/scale": (d,), b + "post_attention_layernorm/scale": (d,)})
        if kind == "conv":
            shapes.update({b + "in_proj": (d, 3 * d), b + "conv": (d, L), b + "out_proj": (d, d)})
        else:
            shapes.update({
                b + "q_proj": (d, H * D), b + "k_proj": (d, KV * D), b + "v_proj": (d, KV * D),
                b + "q_layernorm/scale": (D,), b + "k_layernorm/scale": (D,),
                b + "o_proj": (H * D, d),
            })
        if i < s["first_k_dense_replace"]:
            w = s["intermediate_size"]
            shapes.update({b + "mlp_gate": (d, w), b + "mlp_up": (d, w), b + "mlp_down": (w, d)})
            continue
        shapes.update({
            b + "router": (d, E),
            b + "experts_gate": (Eh, d, f), b + "experts_up": (Eh, d, f),
            b + "experts_down": (Eh, f, d),
        })
        if s["topk_method"] == "noaux_tc":
            shapes[b + "router_bias"] = (E,)
    return shapes


@functools.lru_cache(maxsize=4)
def _maker(shapes, share):
    """One jitted call that draws every leaf from a key."""

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes):
            k = jax.random.fold_in(key, i)
            if name.endswith("/scale"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith("/router_bias"):
                # Normal at BIAS_SCALE, centred over each chip's run of
                # ``share`` experts (kanana-2-30b-a3b's rule): a load
                # balancer's bias moves choices between experts, and here no
                # chip's load with the seed (a run's work must not depend on
                # its seed).
                b = BIAS_SCALE * jax.random.normal(k, shape, jnp.float32)
                out[name] = b - jnp.repeat(jnp.mean(b.reshape(-1, share), axis=1), share)
            elif name.endswith("/conv"):
                # deviation 1/sqrt(L): the sum over the L taps keeps its
                # input's scale, as a 0.02 x sqrt(2048) projection nearly does
                out[name] = shape[1] ** -0.5 * jax.random.normal(k, shape, jnp.float32)
            else:
                # unit-RMS embedding, Mellum's reason: under a 0.02 embedding
                # the mixers' branches carry most of each normed vector and
                # the router follows the seed, not the token
                std = 1.0 if name == "embed_tokens/embedding" else 0.02
                out[name] = std * jax.random.normal(k, shape, jnp.float32)
        return out

    return make


def init_params(seed, cfg):
    s = _spec(cfg)
    make = _maker(tuple(sorted(param_shapes(cfg).items())), s["held"][1] - s["held"][0])
    return make(jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)), 7919))


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _rotate(x, theta):
    """Rotate-half rotary on x [B, T, heads, D] at the default frequencies."""
    T, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb)[None, :, None, :], jnp.sin(emb)[None, :, None, :]
    turned = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + turned * sin


def _short_conv(n, p, b, ops):
    """The gated short convolution on n [B, T, d]."""
    B, T, d = n.shape
    gate_b, gate_c, x = jnp.split(ops.dot(n, p[b + "in_proj"]), 3, axis=-1)
    u = gate_b * x
    w = p[b + "conv"]
    L = w.shape[1]
    # u_{t-(L-1)+j}: L - 1 zeros ahead of position 0, then the window's j-th copy
    padded = jnp.concatenate([jnp.zeros((B, L - 1, d), u.dtype), u], axis=1)
    c = sum(w[None, None, :, j] * padded[:, j:j + T] for j in range(L))
    return ops.dot(gate_c * c, p[b + "out_proj"])


def _attention(n, p, b, s, ops):
    """QK-normed grouped-query attention on n [B, T, d], scores written out
    T x T, one key/value head's query heads at a time."""
    B, T, _ = n.shape
    H, KV, D = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    eps, theta = float(s["rms_norm_eps"]), float(s["rope_theta"])
    q = ops.dot(n, p[b + "q_proj"]).reshape(B, T, H, D)
    k = ops.dot(n, p[b + "k_proj"]).reshape(B, T, KV, D)
    v = ops.dot(n, p[b + "v_proj"]).reshape(B, T, KV, D)
    q = _rotate(_rms(q, p[b + "q_layernorm/scale"], eps), theta)
    k = _rotate(_rms(k, p[b + "k_layernorm/scale"], eps), theta)
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    group, outs = H // KV, []
    for g in range(KV):
        qg = q[:, :, g * group:(g + 1) * group]
        scores = ops.einsum("bqhd,bkd->bhqk", qg, k[:, :, g]).astype(jnp.float32) / math.sqrt(D)
        a = jax.nn.softmax(jnp.where(mask[None, None], scores, -1e30), axis=-1)
        outs.append(ops.einsum("bhqk,bkd->bqhd", a.astype(n.dtype), v[:, :, g]))
    o = jnp.concatenate(outs, axis=2).reshape(B, T, H * D)
    return ops.dot(o, p[b + "o_proj"])


def _gated(ops, x, gate, up, down):
    return ops.dot(jax.nn.silu(ops.dot(x, gate)) * ops.dot(x, up), down)


def _grouped(ops, rows, weights, group_sizes):
    """Row r of the result is ``rows[r] @ weights[g]`` for the group g that r
    lies in; both operands through the control's quantiser, as ``ops.dot``
    puts its own."""
    return jax.lax.ragged_dot(ops._q(rows), ops._q(weights), group_sizes)


def _routed(n, p, b, s, ops):
    """The held experts' part of the routed sum for tokens n [N, d]."""
    N, d = n.shape
    k = int(s["num_experts_per_tok"])
    lo, hi = s["held"]
    scores = jax.nn.sigmoid(ops.dot(n, p[b + "router"]).astype(jnp.float32))
    if s["topk_method"] == "noaux_tc":
        _, experts = jax.lax.top_k(scores + jax.lax.stop_gradient(p[b + "router_bias"]), k)
        values = jnp.take_along_axis(scores, experts, axis=-1)
    else:
        values, experts = jax.lax.top_k(scores, k)
    if s["norm_topk_prob"]:
        values = values / (jnp.sum(values, axis=-1, keepdims=True) + float(s["renorm_eps"]))
    values = values * float(s["routed_scaling_factor"])
    expert = experts.reshape(N * k)
    held = (expert >= lo) & (expert < hi)
    group = jnp.where(held, expert - lo, hi - lo)   # absent experts sort last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.sum(group[:, None] == jnp.arange(hi - lo)[None, :], axis=0, dtype=jnp.int32)

    # Recomputed in the backward pass, not kept (N*k rows of d and of f
    # numbers in float32 for every layer at once). It holds no product that
    # the benchmark's FLOP count sees, so nothing is counted twice.
    @jax.checkpoint
    def held_part(n, values, gate, up, down):
        token = order // k
        live = held[order][:, None]                 # rows inside a group
        rows = jnp.where(live, n[token], 0.0)
        # a grouped product leaves whatever it finds in the rows outside every
        # group (on the chip: not zeros), so each result is cleared there
        hidden = jnp.where(
            live, jax.nn.silu(_grouped(ops, rows, gate, sizes)) * _grouped(ops, rows, up, sizes), 0.0)
        out = jnp.where(live, _grouped(ops, hidden, down, sizes), 0.0)
        weight = values.reshape(N * k)[order][:, None]
        return jnp.zeros((N, d), n.dtype).at[token].add((out * weight).astype(n.dtype))

    return held_part(n, values, p[b + "experts_gate"], p[b + "experts_up"], p[b + "experts_down"])


def logits_fn(p, tokens, ops, cfg):
    s = _spec(cfg)
    B, T = tokens.shape
    d, eps = s["hidden_size"], float(s["rms_norm_eps"])
    x = p["embed_tokens/embedding"][tokens]
    for i, kind in enumerate(s["layer_types"]):
        b = f"layers_{i}/"
        n = _rms(x, p[b + "input_layernorm/scale"], eps)
        x = x + (_short_conv(n, p, b, ops) if kind == "conv" else _attention(n, p, b, s, ops))
        n = _rms(x, p[b + "post_attention_layernorm/scale"], eps)
        if i < s["first_k_dense_replace"]:
            x = x + _gated(ops, n, p[b + "mlp_gate"], p[b + "mlp_up"], p[b + "mlp_down"])
        else:
            x = x + _routed(n.reshape(B * T, d), p, b, s, ops).reshape(B, T, d)
    x = _rms(x, p["norm/scale"], eps)
    return ops.dot(x, p["lm_head/kernel"])


def unit_batch(cfg):
    """Shapes of one real document, for the FLOP count."""
    T = _spec(cfg)["length"]
    return (
        jax.ShapeDtypeStruct((1, T), jnp.int32),
        jax.ShapeDtypeStruct((1, T), jnp.int32),
    )
