"""Plain reference of the stack that ``config.json`` of
Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 describes (NVIDIA; Hugging Face
``nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16``, ``model_type``
``nemotron_h``), given this chip's share of the stated deployment: experts
``experts_held`` of every expert layer and the first ``vocab_size`` rows of
the vocabulary. ONE stack, trained by next-token cross-entropy: no second
tower, no conditioning across towers, no decoding by diffusion (none of
which has a key in ``config.json``; the configuration's ``departures``).

    x0 = E[token]
    x  = x + Part_i(RMSNorm_i(x))        one part a layer, one norm (eps 1e-5)
    logits = RMSNorm_f(x_L) W_head                              untied head

The part is named by character ``i`` of ``hybrid_override_pattern``.

``M``, the Mamba-2 mixer (HF's ``NemotronHMamba2Mixer``), n = RMSNorm(x),
``H`` heads of ``P`` (inner width H P), ``G`` groups, state ``N``:
    [z | xBC | dt] = n W_in                   d -> H P + (H P + 2 G N) + H
    xBC <- SiLU(conv(xBC) + b)                causal depthwise, ``conv_kernel``
                                              taps, zeros before position 0
    [x | B | C] = xBC                         H P | G N | G N; head h reads the
                                              B, C of group h // (H / G)
    step_t = softplus(dt_t + dt_bias)         no clamp; A = -exp(A_log)
    S_t = exp(step_t A) S_{t-1} + step_t x_t B_t^T       S_0 = 0, [P, N] a head
    y_t = S_t C_t + D x_t
    y <- GroupRMSNorm(y * SiLU(z)) * w        groups of H P / G, gate first
    Part = y W_out
  The recurrence is computed AS WRITTEN, position by position: a ``lax.scan``
  over the positions with the state as its carry, elementwise products and
  sums only. (The program computes the same function by chunks,
  ``fedml_tpu/ops/ssd.py``: two different programs of one function.) The
  positions are scanned in blocks, a scan over blocks whose inner scan over a
  block's positions is rematerialised: the backward pass keeps one state a
  block and, for the block it is in, one a position (32 + 128 states of 2 MB
  at T = 4 096, not 4 096 of them: 8.6 GB a layer). The recurrence holds no
  ``dot_general``, so the benchmark's FLOP count (``lib/flops.py``, over the
  jaxpr of THIS file's loss) leaves the state-space core out of
  ``round.mfu_pct``, as it leaves the routed products out, and counts
  nothing twice under the ``checkpoint``; ``ssm.scan_hbm_pct`` holds the core.

``*``, attention WITHOUT positions (``NemotronHAttention``): 32 query heads on
2 key/value heads of 128, no bias, no rotary, no QK norm;
``softmax(q . k / sqrt(128)) v`` under the causal mask; ``W_o``. The scores
are written out ``QUERIES_AT_A_TIME`` queries at a time against the keys up
to the block's last query: the same numbers as the full T x T matrix gives
(the keys after a block are masked for all of it), with the float32
probabilities that the backward pass keeps a little over half of T x T for
every head (1.1 GB of 2.1 at T = 4 096: the reference has to fit beside
``lib/fedavg_ref.py``'s copies of 528 M parameters), and the products that
``round.mfu_pct`` counts those of the causal blocks, 53 % of the full
matrix's (the other language-model references write, and count, T x T).

``E``, the expert part (``NemotronHMOE``): ``s = sigmoid(n W_r)`` in float32
over all experts; the top 6 of ``s + b`` are chosen (``b``, HF's
``e_score_correction_bias``, enters the choice and not the weight; ``n_group``
1); ``w = s[chosen] / (sum s[chosen] + 1e-20)`` times
``routed_scaling_factor``; each expert is UNGATED, ``relu(m W_up)**2
W_down``: two matrices; plus one shared expert of the same form at its own
width that every token takes. What the absent experts would add is left out
(model-configs guide, section 4). The two products of every (token, slot)
pair run as grouped products (``jax.lax.ragged_dot``) over the pairs sorted
by expert, the pairs of absent experts last and outside every group; the
FLOP counter skips ``ragged_dot``, and ``moe.ungated_peak_pct`` holds them.

``-``, a dense ``relu(m W_up)**2 W_down`` alone (no published layer is one).

Every other product is ``ops.dot`` or ``ops.einsum``, which the int8 control
quantises. Departures from the source, each also under ``assumed`` or
``departures`` in the configuration file: the selection bias is a parameter
leaf (HF: a buffer) that nothing updates; no auxiliary loss; all of this
file is float32.

The seed's selection bias is BALANCED, as a checkpoint trained with the
source's bias-update rule would bring it: drawn normal at ``BIAS_SCALE``
and centred over each chip's run of experts, then moved ``BALANCE_STEPS``
times by ``BALANCE_STEP`` against the sign of each expert's excess load
(DeepSeek-V3's auxiliary-loss-free rule, which ``nemotron_h`` routers are
trained under) on ``BALANCE_DOCUMENTS`` seeded uniform documents of the
training length, routed by the seed's own weights in a forward pass of this
file (65 536 tokens: on one document's 4 096 the balanced loads are each
192 +- 14 pairs, and the share this chip's 8 experts take of fresh documents
is off by 2.4 % from that noise alone, as far as the unbalanced router's
was from its imbalance). Why: with
a random router behind Mamba-2 layers (whose SiLU outputs give every
token's residual a common component) a few experts take most pairs (largest
load 1.7-1.9 times the mean over 8 held) and this chip's share of the pairs
moves by 3 % with the seed; the grouped products' time follows the rows in
each group, in tiles: this cell's even share, 4096 x 6 x 8 / 128 = 1 536 rows
a call, is a whole number of them, so a call just over it pays for one tile
more (a tenth of the grouped products' time) and a seed whose share lies 2 %
over or under the even one runs four calls in five on one side. Six runs of
the cell spread by 1.0 % of their median where half the rates' bound is
0.75 % (PERF.md section 6, PR 38). A run's work must not depend on its seed."""

import functools
import json
import math

import jax
import jax.numpy as jnp

# Scale of the selection bias drawn from the seed (see ``init_params``), and
# the balancing pass that starts from it: documents routed, updates, their step.
BIAS_SCALE = 0.015
BALANCE_DOCUMENTS = 16
BALANCE_STEPS = 300
BALANCE_STEP = 0.002
# Queries whose scores against the keys up to them are written out at once.
QUERIES_AT_A_TIME = 256
# Positions in one rematerialised block of the recurrence.
BLOCK = 128


def _spec(cfg):
    m = cfg["model"]
    kw = dict(m["kwargs"])
    kw["vocab_size"] = int(m["num_classes"])
    kw["length"] = int(m["input_shape"][0])
    kw["held"] = tuple(kw.get("experts_held") or (0, kw["n_routed_experts"]))
    return kw


def param_shapes(cfg):
    s = _spec(cfg)
    V, d = s["vocab_size"], s["hidden_size"]
    shapes = {"embed_tokens/embedding": (V, d), "norm/scale": (d,), "lm_head/kernel": (d, V)}
    for i, c in enumerate(s["hybrid_override_pattern"]):
        b = f"layers_{i}/"
        shapes[b + "norm/scale"] = (d,)
        if c == "M":
            H, P, G, N = (s[k] for k in ("mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size"))
            inner, conv = H * P, H * P + 2 * G * N
            shapes.update({
                b + "in_proj": (d, inner + conv + H), b + "conv": (conv, s["conv_kernel"]),
                b + "dt_bias": (H,), b + "A_log": (H,), b + "D": (H,),
                b + "gated_norm": (inner,), b + "out_proj": (inner, d),
            })
            if s.get("use_conv_bias", True):
                shapes[b + "conv_bias"] = (conv,)
        elif c == "*":
            H, KV, D = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
            shapes.update({b + "q_proj": (d, H * D), b + "k_proj": (d, KV * D),
                           b + "v_proj": (d, KV * D), b + "o_proj": (H * D, d)})
        elif c == "E":
            E, f, fs = s["n_routed_experts"], s["moe_intermediate_size"], s["moe_shared_expert_intermediate_size"]
            Eh = s["held"][1] - s["held"][0]
            shapes.update({b + "router": (d, E), b + "router_bias": (E,),
                           b + "experts_up": (Eh, d, f), b + "experts_down": (Eh, f, d),
                           b + "shared_up": (d, fs), b + "shared_down": (fs, d)})
        elif c == "-":
            w = s["intermediate_size"]
            shapes.update({b + "mlp_up": (d, w), b + "mlp_down": (w, d)})
        else:
            raise ValueError(f"unknown part {c!r} in hybrid_override_pattern")
    return shapes


@functools.lru_cache(maxsize=4)
def _maker(shapes, share, dt_min, dt_max, dt_floor):
    """One jitted call that draws every leaf from a key."""

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes):
            k = jax.random.fold_in(key, i)
            leaf = name.rsplit("/", 1)[-1]
            if leaf in ("scale", "gated_norm", "D"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif leaf == "conv_bias":
                out[name] = jnp.zeros(shape, jnp.float32)
            elif leaf == "router_bias":
                # Normal at BIAS_SCALE, centred over each chip's run of
                # ``share`` experts (kanana-2-30b-a3b's rule): a load
                # balancer's bias moves choices between experts, and here no
                # chip's load with the seed (a run's work must not depend on
                # its seed).
                b = BIAS_SCALE * jax.random.normal(k, shape, jnp.float32)
                out[name] = b - jnp.repeat(jnp.mean(b.reshape(-1, share), axis=1), share)
            elif leaf == "conv":
                # deviation 1/sqrt(taps): the sum over the taps keeps its input's scale
                out[name] = shape[1] ** -0.5 * jax.random.normal(k, shape, jnp.float32)
            elif leaf == "A_log":
                # the source's own draw: A = -a, a uniform in [1, 16]
                out[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
            elif leaf == "dt_bias":
                # the inverse softplus of a step drawn log-uniformly in
                # [time_step_min, time_step_max] and floored at time_step_floor
                u = jax.random.uniform(k, shape, jnp.float32)
                dt = jnp.exp(u * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
                dt = jnp.maximum(dt, dt_floor)
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
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
    make = _maker(
        tuple(sorted(param_shapes(cfg).items())), s["held"][1] - s["held"][0],
        float(s.get("time_step_min", 0.001)), float(s.get("time_step_max", 0.1)),
        float(s.get("time_step_floor", 1e-4)))
    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)), 7919)
    return _balanced(make(key), key, json.dumps(cfg["model"], sort_keys=True))


class _Plain:
    """How the balancing pass multiplies: as written, nothing quantised."""

    _q = staticmethod(lambda x: x)
    dot = staticmethod(jnp.dot)
    einsum = staticmethod(jnp.einsum)


@functools.lru_cache(maxsize=4)
def _balancer(model_json):
    """One jitted call that gives every expert part's selection bias as a
    load balancer would have left it: {bias leaf: [experts]}."""
    cfg = {"model": json.loads(model_json)}
    s = _spec(cfg)
    k, V, T = int(s["num_experts_per_tok"]), s["vocab_size"], s["length"]

    @jax.jit
    def balance(p, key):
        # documents as the feed draws them: uniform ids 1..V-1
        tokens = jax.random.randint(
            jax.random.fold_in(key, 104729), (BALANCE_DOCUMENTS, 1, T), 1, V)

        def routed(document):
            scores = {}
            _forward(p, document, _Plain, cfg, scores)
            return scores

        # a document at a time, so that the pass holds one document's activations
        scores = {name: sc.reshape(-1, sc.shape[-1])
                  for name, sc in jax.lax.map(routed, tokens).items()}

        def balanced(sc, bias):
            def step(b, _):
                _, chosen = jax.lax.top_k(sc + b, k)
                load = jnp.sum(jax.nn.one_hot(chosen, sc.shape[1], dtype=jnp.float32), axis=(0, 1))
                return b - BALANCE_STEP * jnp.sign(load - jnp.mean(load)), None

            return jax.lax.scan(step, bias, None, length=BALANCE_STEPS)[0]

        return {name: balanced(sc, p[name]) for name, sc in scores.items()}

    return balance


def _balanced(p, key, model_json):
    """The seed's weights with each selection bias balanced (see the top of
    this file). At the highest matmul precision whatever the caller's
    setting: the program's seed weights and the reference's are two calls of
    this function, and have to come out the same to the bit."""
    if not any(name.endswith("/router_bias") for name in p):
        return p
    with jax.default_matmul_precision("highest"):
        return {**p, **_balancer(model_json)(p, key)}


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _recurrence(x, step, A, B, C, D):
    """The state-space recurrence of one sequence, position by position:
    x [T, H, P], step [T, H], A and D [H], B and C [T, G, N] -> y [T, H, P]."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    per = H // G

    def position(S, at):
        x_t, step_t, B_t, C_t = at
        B_h, C_h = jnp.repeat(B_t, per, axis=0), jnp.repeat(C_t, per, axis=0)   # [H, N]
        S = jnp.exp(step_t * A)[:, None, None] * S \
            + (step_t[:, None] * x_t)[:, :, None] * B_h[:, None, :]
        y_t = jnp.sum(S * C_h[:, None, :], axis=-1) + D[:, None] * x_t
        return S, y_t

    @jax.checkpoint
    def block(S, ats):
        return jax.lax.scan(position, S, ats)

    size = next(b for b in range(min(BLOCK, T), 0, -1) if T % b == 0)
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape(T // size, size, *a.shape[1:]), (x, step, B, C))
    _, y = jax.lax.scan(block, jnp.zeros((H, P, N), x.dtype), blocks)
    return y.reshape(T, H, P)


def _mamba_core(zxbcdt, w, bias, dt_bias, A_log, D, norm_scale, *, H, P, G, N, eps):
    """Everything of the mixer between its two projections, on the input
    projection's output [B, T, inner + conv + H]: convolution, recurrence,
    gated group norm. No product of matrices is in here."""
    Bt, T, _ = zxbcdt.shape
    inner, L = H * P, w.shape[1]
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * G * N], axis=-1)
    # xBC_{t-(L-1)+j}: L - 1 zeros ahead of position 0, then the window's j-th copy
    padded = jnp.concatenate([jnp.zeros((Bt, L - 1, xbc.shape[-1]), xbc.dtype), xbc], axis=1)
    conv = sum(w[None, None, :, j] * padded[:, j:j + T] for j in range(L))
    if bias is not None:
        conv = conv + bias
    x, Bm, Cm = jnp.split(jax.nn.silu(conv), [inner, inner + G * N], axis=-1)
    step = jax.nn.softplus(dt + dt_bias)
    y = jax.vmap(_recurrence, in_axes=(0, 0, None, 0, 0, None))(
        x.reshape(Bt, T, H, P), step, -jnp.exp(A_log),
        Bm.reshape(Bt, T, G, N), Cm.reshape(Bt, T, G, N), D)
    gated = (y.reshape(Bt, T, inner) * jax.nn.silu(z)).reshape(Bt, T, G, inner // G)
    gated = gated * jax.lax.rsqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + eps)
    return gated.reshape(Bt, T, inner) * norm_scale


def _mamba(n, p, b, s, ops):
    """The Mamba-2 mixer on n [B, T, d]. The core between the projections is
    recomputed in the backward pass, not kept (a dozen float32 arrays of
    [T, 4096] to [T, 10304] a layer, 1.2 GB at T = 4 096, for all three
    layers at once): it holds no product that the benchmark's FLOP count
    sees, so nothing is counted twice."""
    H, P, G, N = (s[k] for k in ("mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size"))
    core = jax.checkpoint(functools.partial(
        _mamba_core, H=H, P=P, G=G, N=N, eps=float(s["rms_norm_eps"])))
    gated = core(
        ops.dot(n, p[b + "in_proj"]), p[b + "conv"],
        p[b + "conv_bias"] if s.get("use_conv_bias", True) else None,
        p[b + "dt_bias"], p[b + "A_log"], p[b + "D"], p[b + "gated_norm"])
    return ops.dot(gated, p[b + "out_proj"])


def _attention(n, p, b, s, ops):
    """Grouped-query attention without positions on n [B, T, d]. The scores
    are written out for ``QUERIES_AT_A_TIME`` queries at a time against the
    keys up to the block's last query (the later keys are masked for every
    query of the block: their probabilities are zeros that nothing reads),
    all query heads of one key/value head together, under the causal mask
    within the block's span."""
    B, T, _ = n.shape
    H, KV, D = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    q = ops.dot(n, p[b + "q_proj"]).reshape(B, T, H, D)
    k = ops.dot(n, p[b + "k_proj"]).reshape(B, T, KV, D)
    v = ops.dot(n, p[b + "v_proj"]).reshape(B, T, KV, D)
    group = H // KV
    size = next(m for m in range(min(QUERIES_AT_A_TIME, T), 0, -1) if T % m == 0)
    blocks = []
    for lo in range(0, T, size):
        hi = lo + size
        mask = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        heads = []
        for g in range(KV):
            scores = ops.einsum("bqhd,bkd->bhqk", q[:, lo:hi, g * group:(g + 1) * group], k[:, :hi, g])
            scores = scores.astype(jnp.float32) / math.sqrt(D)
            a = jax.nn.softmax(jnp.where(mask[None, None], scores, -1e30), axis=-1)
            heads.append(ops.einsum("bhqk,bkd->bqhd", a.astype(n.dtype), v[:, :hi, g]))
        blocks.append(jnp.concatenate(heads, axis=2))
    o = jnp.concatenate(blocks, axis=1).reshape(B, T, H * D)
    return ops.dot(o, p[b + "o_proj"])


def _relu2(ops, x, up, down):
    return ops.dot(jnp.square(jax.nn.relu(ops.dot(x, up))), down)


def _grouped(ops, rows, weights, group_sizes):
    """Row r of the result is ``rows[r] @ weights[g]`` for the group g that r
    lies in; both operands through the control's quantiser, as ``ops.dot``
    puts its own."""
    return jax.lax.ragged_dot(ops._q(rows), ops._q(weights), group_sizes)


def _routed(n, p, b, s, ops, scores_out=None):
    """The held experts' part of the routed sum for tokens n [N, d]."""
    N, d = n.shape
    k = int(s["num_experts_per_tok"])
    lo, hi = s["held"]
    scores = jax.nn.sigmoid(ops.dot(n, p[b + "router"]).astype(jnp.float32))
    if scores_out is not None:
        scores_out[b + "router_bias"] = scores
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
    def held_part(n, values, up, down):
        token = order // k
        live = held[order][:, None]                 # rows inside a group
        rows = jnp.where(live, n[token], 0.0)
        # a grouped product leaves whatever it finds in the rows outside every
        # group (on the chip: not zeros), so each result is cleared there
        hidden = jnp.where(live, jnp.square(jax.nn.relu(_grouped(ops, rows, up, sizes))), 0.0)
        out = jnp.where(live, _grouped(ops, hidden, down, sizes), 0.0)
        weight = values.reshape(N * k)[order][:, None]
        return jnp.zeros((N, d), n.dtype).at[token].add((out * weight).astype(n.dtype))

    return held_part(n, values, p[b + "experts_up"], p[b + "experts_down"])


def _experts(n, p, b, s, ops, scores_out=None):
    """The expert part on n [B, T, d]: the held routed experts' sum plus the
    shared expert, which every token takes."""
    B, T, d = n.shape
    routed = _routed(n.reshape(B * T, d), p, b, s, ops, scores_out).reshape(B, T, d)
    return routed + _relu2(ops, n, p[b + "shared_up"], p[b + "shared_down"])


def logits_fn(p, tokens, ops, cfg):
    return _forward(p, tokens, ops, cfg)


def _forward(p, tokens, ops, cfg, scores_out=None):
    """The logits; with ``scores_out`` a dict, also every expert part's
    router scores [tokens, experts] under its bias leaf's name."""
    s = _spec(cfg)
    eps = float(s["rms_norm_eps"])
    x = p["embed_tokens/embedding"][tokens]
    for i, c in enumerate(s["hybrid_override_pattern"]):
        b = f"layers_{i}/"
        n = _rms(x, p[b + "norm/scale"], eps)
        if c == "M":
            x = x + _mamba(n, p, b, s, ops)
        elif c == "*":
            x = x + _attention(n, p, b, s, ops)
        elif c == "E":
            x = x + _experts(n, p, b, s, ops, scores_out)
        else:
            x = x + _relu2(ops, n, p[b + "mlp_up"], p[b + "mlp_down"])
    x = _rms(x, p["norm/scale"], eps)
    return ops.dot(x, p["lm_head/kernel"])


def unit_batch(cfg):
    """Shapes of one real document, for the FLOP count."""
    T = _spec(cfg)["length"]
    return (
        jax.ShapeDtypeStruct((1, T), jnp.int32),
        jax.ShapeDtypeStruct((1, T), jnp.int32),
    )
