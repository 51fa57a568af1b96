"""Plain reference of the Kanana-2-30B-A3B decoder (kakaocorp; Hugging Face
``kakaocorp/kanana-2-30b-a3b-instruct-2601``, ``config.json``, ``model_type``
``deepseek_v3``), given this chip's share of the stated deployment: experts
``experts_held`` of every expert layer and the first ``vocab_size`` rows of
the vocabulary. The equations are HF's ``DeepseekV3`` with this config:

    x0 = E[token]
    n  = RMSNorm(x), eps 1e-6
    q  = n Wq                     -> a head: (q_nope 128 | q_rope 64); no query latent
    ckv, k_rope = split(n Wkva)   -> 512 and 64: ONE rotary key for all 32 heads
    kv = RMSNorm_512(ckv) Wkvb    -> a head: (k_nope 128 | v 128)
    rotary on q_rope and k_rope only (below)
    s_ij = (q_nope_i . k_nope_j + q_rope_i . k_rope_j) / sqrt(192), mask j <= i
    a  = softmax(s) v;            h = x + a Wo                (Wo: 32 x 128 -> 2048)
    layer 0 (first_k_dense_replace 1):  y = h + Wdown(silu(Wgate m) * Wup m), width 6144
    layers 1+:  y = h + Routed(m) + Shared(m)                  m = RMSNorm(h)
    logits = RMSNorm(y_L) W_head                               untied head

Rotary (``rope_interleave`` true, ``rope_scaling`` null: the default type):
the 64 rope dims are de-interleaved, ``[x0, x2, .. | x1, x3, ..]`` (HF's
``apply_rotary_pos_emb_interleave``), then rotate-half with ``inv_freq_m =
theta ** (-2m/64)``, theta 1e6.

Routed (HF's ``DeepseekV3TopkRouter`` with ``n_group`` = ``topk_group`` = 1,
so its group step keeps everything): ``s = sigmoid(m Wr)`` over all 128 in
float32; the top 6 of ``s + b`` are chosen (``b``: ``e_score_correction_bias``,
selection only); ``w = s[chosen] / (sum s[chosen] + 1e-20) * 2.448``;
``Routed(m) = sum over the chosen slots whose expert is held here of w_slot *
Wdown_e (silu(Wgate_e m) * Wup_e m)``, width 768. No capacity, no dropped
pair; what the absent experts would add is left out (model-configs guide,
section 4). Shared: one gated-SiLU MLP of width 2 x 768 on every token, which
every chip of the deployment computes alike.

The three products of every (token, slot) pair run as grouped products
(``jax.lax.ragged_dot``) over the pairs sorted by expert, the pairs of absent
experts last and outside every group, and not as every held expert on every
token under a mask: the benchmark counts the FLOPs that ``round.mfu_pct``
divides by the peak in the jaxpr of THIS file's loss, and the masked form
would count 8 experts a token where routing requires 6 x 8 / 128. The counter
skips ``ragged_dot``, so ``round.mfu_pct`` leaves the routed products out and
``moe.expert_peak_pct`` holds them. Every other product is ``ops.dot`` or
``ops.einsum``, which the int8 control quantises; attention is counted full
T x T as written.

Departures from the source, each also under ``assumed`` in the configuration
file: the selection bias is a parameter leaf (HF: a buffer) that nothing
updates (its gradient is zero: it only picks indices); no MTP head and no
auxiliary loss; the two rotary operands are rotated in float32."""

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
    kw["held"] = tuple(kw.get("experts_held") or (0, kw["n_routed_experts"]))
    return kw


def param_shapes(cfg):
    s = _spec(cfg)
    V, d, H = s["vocab_size"], s["hidden_size"], s["num_attention_heads"]
    nope, rope, vd = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    rank, E, f = s["kv_lora_rank"], s["n_routed_experts"], s["moe_intermediate_size"]
    Eh = s["held"][1] - s["held"][0]
    shapes = {"embed_tokens/embedding": (V, d), "norm/scale": (d,), "lm_head/kernel": (d, V)}
    for i in range(s["num_hidden_layers"]):
        b = f"layers_{i}/"
        shapes.update({
            b + "input_layernorm/scale": (d,), b + "post_attention_layernorm/scale": (d,),
            b + "q_proj": (d, H * (nope + rope)), b + "kv_a_proj": (d, rank + rope),
            b + "kv_a_layernorm/scale": (rank,), b + "kv_b_proj": (rank, H * (nope + vd)),
            b + "o_proj": (H * vd, d),
        })
        if i < s["first_k_dense_replace"]:
            w = s["intermediate_size"]
            shapes.update({b + "mlp_gate": (d, w), b + "mlp_up": (d, w), b + "mlp_down": (w, d)})
            continue
        w = s["n_shared_experts"] * f
        shapes.update({
            b + "router": (d, E), b + "router_bias": (E,),
            b + "experts_gate": (Eh, d, f), b + "experts_up": (Eh, d, f),
            b + "experts_down": (Eh, f, d),
            b + "shared_gate": (d, w), b + "shared_up": (d, w), b + "shared_down": (w, d),
        })
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
                # ``share`` experts: a load balancer's bias moves choices
                # between experts, and here no chip's load with the seed (a
                # run's work must not depend on its seed). At 0.015 under
                # sigmoid scores of logits with deviation 0.9 it moves about a
                # tenth of the chosen pairs (``moe.bias_moved_pair_pct``).
                b = BIAS_SCALE * jax.random.normal(k, shape, jnp.float32)
                out[name] = b - jnp.repeat(jnp.mean(b.reshape(-1, share), axis=1), share)
            else:
                # unit-RMS embedding, Mellum's reason: under a 0.02 embedding
                # the attention branch's mean over the context carries most of
                # each normed vector and the router follows the seed, not the token
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


def _rotate(x, theta, interleave):
    """HF's rotary on x [B, T, heads, R]: de-interleaved first where the
    config says so, then rotate-half at the default frequencies."""
    T, R = x.shape[1], x.shape[-1]
    if interleave:
        x = jnp.swapaxes(x.reshape(x.shape[:-1] + (R // 2, 2)), -1, -2).reshape(x.shape)
    inv = theta ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb)[None, :, None, :], jnp.sin(emb)[None, :, None, :]
    turned = jnp.concatenate([-x[..., R // 2:], x[..., :R // 2]], axis=-1)
    return x * cos + turned * sin


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
    if s["scoring_func"] != "sigmoid" or s["topk_method"] != "noaux_tc":
        raise ValueError("this reference is the sigmoid, noaux_tc router")
    if s["n_group"] != 1 or s["topk_group"] != 1:
        raise ValueError("this reference has no group-limited routing")
    scores = jax.nn.sigmoid(ops.dot(n, p[b + "router"]).astype(jnp.float32))
    _, experts = jax.lax.top_k(scores + jax.lax.stop_gradient(p[b + "router_bias"]), k)
    values = jnp.take_along_axis(scores, experts, axis=-1)
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
    if s.get("q_lora_rank") is not None or s.get("rope_scaling") is not None:
        raise ValueError("this reference has no query latent and no rotary scaling")
    B, T = tokens.shape
    d, H = s["hidden_size"], s["num_attention_heads"]
    nope, rope, vd = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    rank, eps, theta = s["kv_lora_rank"], float(s["rms_norm_eps"]), float(s["rope_theta"])
    mask = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    x = p["embed_tokens/embedding"][tokens]
    for i in range(s["num_hidden_layers"]):
        b = f"layers_{i}/"
        n = _rms(x, p[b + "input_layernorm/scale"], eps)
        q = ops.dot(n, p[b + "q_proj"]).reshape(B, T, H, nope + rope)
        down = ops.dot(n, p[b + "kv_a_proj"])
        latent = _rms(down[..., :rank], p[b + "kv_a_layernorm/scale"], eps)
        kv = ops.dot(latent, p[b + "kv_b_proj"]).reshape(B, T, H, nope + vd)
        q_rope = _rotate(q[..., nope:], theta, s["rope_interleave"])
        k_rope = _rotate(down[..., None, rank:], theta, s["rope_interleave"])[:, :, 0]
        scores = (
            ops.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope])
            + ops.einsum("bqhr,bkr->bhqk", q_rope, k_rope)
        ).astype(jnp.float32) / math.sqrt(nope + rope)
        a = jax.nn.softmax(jnp.where(mask[None, None], scores, -1e30), axis=-1)
        o = ops.einsum("bhqk,bkhd->bqhd", a.astype(x.dtype), kv[..., nope:]).reshape(B, T, H * vd)
        x = x + ops.dot(o, p[b + "o_proj"])
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
