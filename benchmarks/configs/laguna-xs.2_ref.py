"""Plain reference of the Laguna-XS.2 decoder (poolside; Hugging Face
``poolside/Laguna-XS.2``, ``config.json``, ``model_type`` ``laguna``), given
this chip's share of the stated deployment: experts ``experts_held`` of every
expert layer and the first ``vocab_size`` rows of the vocabulary.

    x0 = E[token]
    h  = x + Wo . Attn_i(n Wq, n Wk, n Wv)       n = RMSNorm(x), eps 1e-6
    y  = h + FFN_i(RMSNorm(h))
    logits = RMSNorm(y_L) W_head                  untied head

Attention of layer i: ``num_attention_heads_per_layer[i]`` query heads (48 on
a full layer, 64 on a sliding one) on 8 key/value heads of 128, no bias, no
QK norm; rotary on q and k as HF applies a partial one:
``cat(x_rot * cos + rotate_half(x_rot) * sin, x_pass)`` with ``x_rot`` the
first ``R = head_dim x partial_rotary_factor`` dims of a head (the kind's
own factor in ``rope_parameters``) and ``rotate_half`` over those ``R``;
scores ``q . k / sqrt(128)``, mask ``j <= i`` and on sliding layers also
``i - j < sliding_window``; softmax; P v.

Rotary tables of a kind, over its ``R`` dims: ``inv_m = theta ** (-2m/R)``.
``default``: as it is. ``yarn`` (HF's ``_compute_yarn_parameters`` with
``dim = R``): ``low, high`` the correction range of ``beta_fast``,
``beta_slow`` over ``original_max_position_embeddings`` (floored and ceiled,
HF's ``truncate`` default), ``ramp = clip((m - low)/(high - low), 0, 1)``,
``inv = (inv / factor) * ramp + inv * (1 - ramp)``, cos and sin times
``attention_factor``: the factor scales the turned dims and not ``x_pass``.

The scores are written ``QUERIES_AT_A_TIME`` queries at a time against the
keys the mask leaves them (up to the block's last query; on a sliding layer
from ``sliding_window - 1`` before its first): the same numbers as the full
T x T matrix gives. Each block is a ``jax.checkpoint``: the backward pass
keeps q, k and v and writes a block's float32 probabilities again when it
reaches it, where keeping them would take 3.4 GB for the two full layers and
2.4 GB for the three sliding ones at T = 4 096, beside
``lib/fedavg_ref.py``'s five float32 copies of 489.7 M parameters (9.8 GB).
The backward recomputes the block's score product and not its value product
(nothing reads that); ``round.mfu_pct`` counts what is written, so it counts
the score product twice: one core product in six more than the work
requires (PERF.md section 4).

Feed-forward of layer i: layer 0 (``mlp_layer_types`` ``dense``) a gated-SiLU
MLP of 8 192, ``Wdown(silu(Wgate m) * Wup m)``; layers 1+ ``Routed(m) +
Shared(m)``: ``s = sigmoid(m Wr)`` over all 256 experts in float32, the top
8 of ``s`` (no selection bias), ``w = s[chosen] / (sum s[chosen] + 1e-20) *
2.5``; ``Routed(m) = sum over the chosen slots whose expert is held here of
w_slot * Wdown_e (silu(Wgate_e m) * Wup_e m)``, width 512; ``Shared`` one
gated-SiLU MLP of 512 on every token, which every chip of the deployment
computes alike. No capacity, no dropped pair; what the absent experts would
add is left out (model-configs guide, section 4). The three products of
every (token, slot) pair run as grouped products (``jax.lax.ragged_dot``)
over the pairs sorted by expert, the pairs of absent experts last and
outside every group: the FLOP counter skips ``ragged_dot``, and
``moe.expert_peak_pct`` holds them.

Every other product is ``ops.dot`` or ``ops.einsum``, which the int8 control
quantises. Departures from the source, each also under ``assumed`` in the
configuration file: no attention output gate (``gating`` read as gated
feed-forwards), no auxiliary loss, no MTP head; all of this file is float32."""

import functools
import math

import jax
import jax.numpy as jnp

# Queries whose scores against the keys the mask leaves them are written out
# at once.
QUERIES_AT_A_TIME = 256


def _spec(cfg):
    m = cfg["model"]
    kw = dict(m["kwargs"])
    kw["vocab_size"] = int(m["num_classes"])
    kw["length"] = int(m["input_shape"][0])
    kw["held"] = tuple(kw.get("experts_held") or (0, kw["num_experts"]))
    return kw


def param_shapes(cfg):
    s = _spec(cfg)
    V, d = s["vocab_size"], s["hidden_size"]
    KV, D = s["num_key_value_heads"], s["head_dim"]
    E, f, fs = s["num_experts"], s["moe_intermediate_size"], s["moe_shared_expert_intermediate_size"]
    Eh = s["held"][1] - s["held"][0]
    shapes = {"embed_tokens/embedding": (V, d), "norm/scale": (d,), "lm_head/kernel": (d, V)}
    for i, H in enumerate(s["num_attention_heads_per_layer"]):
        b = f"layers_{i}/"
        shapes.update({
            b + "input_layernorm/scale": (d,), b + "post_attention_layernorm/scale": (d,),
            b + "q_proj": (d, H * D), b + "k_proj": (d, KV * D), b + "v_proj": (d, KV * D),
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
            b + "shared_gate": (d, fs), b + "shared_up": (d, fs), b + "shared_down": (fs, d),
        })
    return shapes


@functools.lru_cache(maxsize=4)
def _maker(shapes):
    """One jitted call that draws every leaf from a key."""

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes):
            if name.endswith("/scale"):
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                # unit-RMS embedding, Mellum's reason: under a 0.02 embedding
                # the attention branch's mean over the context carries most of
                # each normed vector and the router follows the seed, not the token
                std = 1.0 if name == "embed_tokens/embedding" else 0.02
                out[name] = std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return make


def init_params(seed, cfg):
    make = _maker(tuple(sorted(param_shapes(cfg).items())))
    return make(jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)), 7919))


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _tables(rope, D, T):
    """(cos, sin) [T, R] of one layer kind, R its turned dims."""
    R = int(D * float(rope.get("partial_rotary_factor", 1.0)))
    theta = float(rope["rope_theta"])
    inv = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    scale = 1.0
    if rope.get("rope_type", "default") == "yarn":
        original = float(rope["original_max_position_embeddings"])

        def correction(rotations):
            return R * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(correction(float(rope["beta_fast"]))), 0)
        high = min(math.ceil(correction(float(rope["beta_slow"]))), R - 1)
        ramp = jnp.clip((jnp.arange(R // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
        inv = (inv / float(rope["factor"])) * ramp + inv * (1.0 - ramp)
        scale = float(rope["attention_factor"])
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb) * scale, jnp.sin(emb) * scale


def _rotate(x, cos, sin):
    """HF's partial rotary on x [B, T, heads, D]: the first R dims turned."""
    R = cos.shape[-1]
    rot, keep = x[..., :R], x[..., R:]
    turned = jnp.concatenate([-rot[..., R // 2:], rot[..., :R // 2]], axis=-1)
    return jnp.concatenate([rot * cos[None, :, None, :] + turned * sin[None, :, None, :], keep], -1)


def _attention(q, k, v, window, ops):
    """Causal attention (and ``i - j < window`` where a window is given) of q
    [B, T, H, D] on k and v [B, T, KV, D], the H / KV query heads of a
    key/value head side by side, ``QUERIES_AT_A_TIME`` queries a block
    against the keys the mask leaves them, each block a ``jax.checkpoint``."""
    B, T, H, D = q.shape
    KV = k.shape[2]
    size = next(m for m in range(min(QUERIES_AT_A_TIME, T), 0, -1) if T % m == 0)

    @functools.partial(jax.checkpoint, static_argnums=(3, 4))
    def block(qb, kb, vb, lo, first):
        hi = lo + qb.shape[1]
        i_pos = jnp.arange(lo, hi)[:, None]
        j_pos = jnp.arange(first, hi)[None, :]
        mask = j_pos <= i_pos
        if window is not None:
            mask = mask & (i_pos - j_pos < window)
        scores = ops.einsum(
            "bqkgd,bskd->bkgqs", qb.reshape(B, hi - lo, KV, H // KV, D), kb
        ).astype(jnp.float32) / math.sqrt(D)
        a = jax.nn.softmax(jnp.where(mask[None, None, None], scores, -1e30), axis=-1)
        return ops.einsum("bkgqs,bskd->bqkgd", a.astype(qb.dtype), vb).reshape(B, hi - lo, H * D)

    out = []
    for lo in range(0, T, size):
        first = 0 if window is None else max(0, lo - (window - 1))
        hi = lo + size
        out.append(block(q[:, lo:hi], k[:, first:hi], v[:, first:hi], lo, first))
    return jnp.concatenate(out, axis=1)


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
    if s["scoring_func"] != "sigmoid" or s.get("topk_method", "greedy") != "greedy":
        raise ValueError("this reference is the sigmoid router without a selection bias")
    scores = jax.nn.sigmoid(ops.dot(n, p[b + "router"]).astype(jnp.float32))
    values, experts = jax.lax.top_k(scores, k)
    if s["norm_topk_prob"]:
        values = values / (jnp.sum(values, axis=-1, keepdims=True) + 1e-20)
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
    d, KV, D = s["hidden_size"], s["num_key_value_heads"], s["head_dim"]
    eps = float(s["rms_norm_eps"])
    tables = {kind: _tables(s["rope_parameters"][kind], D, T) for kind in set(s["layer_types"])}
    x = p["embed_tokens/embedding"][tokens]
    for i, (kind, H) in enumerate(zip(s["layer_types"], s["num_attention_heads_per_layer"])):
        b = f"layers_{i}/"
        if kind not in ("full_attention", "sliding_attention"):
            raise ValueError(f"unknown layer kind {kind!r}")
        n = _rms(x, p[b + "input_layernorm/scale"], eps)
        q = ops.dot(n, p[b + "q_proj"]).reshape(B, T, H, D)
        k = ops.dot(n, p[b + "k_proj"]).reshape(B, T, KV, D)
        v = ops.dot(n, p[b + "v_proj"]).reshape(B, T, KV, D)
        q, k = (_rotate(a, *tables[kind]) for a in (q, k))
        window = int(s["sliding_window"]) if kind == "sliding_attention" else None
        x = x + ops.dot(_attention(q, k, v, window, ops), p[b + "o_proj"])
        n = _rms(x, p[b + "post_attention_layernorm/scale"], eps)
        if i < s["first_k_dense_replace"]:
            x = x + _gated(ops, n, p[b + "mlp_gate"], p[b + "mlp_up"], p[b + "mlp_down"])
            continue
        x = x + _routed(n.reshape(B * T, d), p, b, s, ops).reshape(B, T, d) + _gated(
            ops, n, p[b + "shared_gate"], p[b + "shared_up"], p[b + "shared_down"])
    x = _rms(x, p["norm/scale"], eps)
    return ops.dot(x, p["lm_head/kernel"])


def unit_batch(cfg):
    """Shapes of one real document, for the FLOP count."""
    T = _spec(cfg)["length"]
    return (
        jax.ShapeDtypeStruct((1, T), jnp.int32),
        jax.ShapeDtypeStruct((1, T), jnp.int32),
    )
