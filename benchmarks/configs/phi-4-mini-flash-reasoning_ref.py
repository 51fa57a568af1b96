"""Plain reference of the SambaY stack that ``config.json`` of
Phi-4-mini-flash-reasoning describes (Microsoft; Hugging Face
``microsoft/Phi-4-mini-flash-reasoning``, ``model_type`` ``phi4flash``;
arXiv:2507.06607), given this chip's share of the stated deployment: the
published layers ``layers_held`` (a contiguous pipeline stage) and the first
``vocab_size`` rows of the tied vocabulary.

    x0 = E[token]
    x  = x + Mixer_l(LN(x))             LN: LayerNorm with scale and bias, eps 1e-5
    x  = x + (SiLU(n W_gate) * n W_up) W_down                 n = LN(x), no bias
    logits = LN_f(x_L) E^T                                    tied head

Layer ``l`` (published index, ``L = num_hidden_layers``, ``half = L // 2``) is
a Mamba layer where ``l % mb_per_layer == 0`` and attention otherwise. Before
``half`` attention is windowed (``i - j < sliding_window``); at ``half`` the
Mamba layer keeps its scan output ``y`` as the memory, at ``half + 1`` full
attention keeps its keys and values; from ``half + 2`` on a Mamba layer is a
Gated Memory Unit and attention is cross-attention over those keys and values.

Mamba-1 (arXiv:2312.00752), inner width ``E = mamba_expand x d``, state
``N = mamba_d_state``, ``R = ceil(d / 16)``:
    [u | z] = n W_in;  u = SiLU(conv(u) + b)       causal depthwise, zeros before 0
    [dt | B | C] = u W_x;  delta = softplus(dt W_dt + b_dt);  A = -exp(A_log) [E, N]
    h_t = exp(delta_t A) * h_{t-1} + (delta_t u_t) B_t^T        h_0 = 0, [E, N]
    y_t = h_t C_t + D u_t;  Mixer = (y * SiLU(z)) W_out
  The recurrence is computed AS WRITTEN, position by position: a ``lax.scan``
  over the positions with the state as its carry, elementwise products and
  sums only (the program runs ``fedml_tpu/ops/selective_scan.py``'s kernels:
  two programs of one function). The positions are scanned in blocks of
  ``BLOCK`` whose inner scan is rematerialised, so the backward pass keeps
  one state a block and, for the block it is in, one a position (32 + 128
  states of 0.33 MB at T = 4 096, not 4 096 of them: 1.34 GB).
Gated Memory Unit: Mixer = (SiLU(n W_in) * memory) W_out.

Differential attention (arXiv:2410.05258), ``H`` query heads and ``KV``
key/value heads of ``D``, no rotary, no bias:
    [q | k | v] = n Wqkv  (a cross layer: q = n Wq, k and v the kept ones)
    q1, q2 = heads [0, H/2), [H/2, H); k1, k2 and v1, v2 likewise of KV
    V_g = [v1_g | v2_g]                                  2 D wide
    o_i = softmax(q1_i k1_g^T / sqrt(D)) V_g - lambda softmax(q2_i k2_g^T / sqrt(D)) V_g
          g = i // 2, causal (windowed before ``half``)
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
    lambda_init = 0.8 - 0.6 exp(-0.3 l)
    o_i <- RMSNorm(o_i) * w * (1 - lambda_init)          over 2 D, eps 1e-5
    Mixer = [o_0 | ... | o_{H/2-1}] W_out
  Both maps' scores are written ``QUERIES_AT_A_TIME`` queries at a time
  against the keys the mask leaves them (up to the block's last query; under
  the window from ``sliding_window - 1`` before its first): the same numbers
  as the full T x T matrix gives. Each block (both maps, their difference
  and its norm) is a ``jax.checkpoint``: the backward pass keeps q, k and v
  and writes a block's float32 probabilities again when it reaches it, where
  keeping them would take 2.8 GB for the full and the cross layer at
  T = 4 096.

Memory. The local step has to load beside ``lib/fedavg_ref.py``'s five
float32 copies of 577.2 M parameters (10.75 GiB of the chip's 15.75: the
round's parameters, the seed's, the average, the previous block's result and
this one's), and on a TPU v5e only 5.00 GiB were left for it: its plan is
3.70 GiB (compiled for a described v5e) beside 274 MiB of code, where
keeping the attention blocks' probabilities and maps and the MLPs' two
products planned 5.56 GiB beside 677 MiB of code (the blocks unrolled one
map at a time), which did not load there. The backward pass so computes four
products twice: each attention block's score and value products, and each
MLP's ``n W_gate`` and ``n W_up`` (``_mlp``). ``round.mfu_pct`` counts what is
written, so it counts them twice: 17.73 TFLOP a document, 2.50 (16 %) over the
15.23 that three times the forward's 5.08 gives (PERF.md section 4).

Every product of matrices is ``ops.dot`` or ``ops.einsum``, or an operand of
``_mlp`` through ``ops._q``, which the int8 control quantises. All of this
file is float32."""

import functools
import math

import jax
import jax.numpy as jnp

# Queries whose scores against the keys the mask leaves them are written at once.
QUERIES_AT_A_TIME = 512
# Positions in one rematerialised block of the recurrence.
BLOCK = 128


def _spec(cfg):
    m = cfg["model"]
    kw = dict(m["kwargs"])
    kw["vocab_size"] = int(m["num_classes"])
    kw["length"] = int(m["input_shape"][0])
    return kw


def _layers(s):
    """(published index, kind, keeps) of each held layer, by the rules above."""
    L, every = int(s["num_hidden_layers"]), int(s["mb_per_layer"])
    half = L // 2
    out = []
    for l in range(*s["layers_held"]):
        if l % every == 0:
            out.append((l, "gmu", False) if l >= half + 2 else (l, "mamba", l >= half))
        elif l >= half + 2:
            out.append((l, "cross", False))
        else:
            out.append((l, "window", False) if l < half else (l, "full", True))
    return out


def _sizes(s):
    d = s["hidden_size"]
    return d, s["mamba_expand"] * d, s["mamba_d_state"], -(-d // 16)


def param_shapes(cfg):
    s = _spec(cfg)
    V = s["vocab_size"]
    d, E, N, R = _sizes(s)
    H, KV, D, f = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"], s["intermediate_size"]
    shapes = {"embed_tokens/embedding": (V, d), "norm/scale": (d,), "norm/bias": (d,)}
    for i, (_, kind, _) in enumerate(_layers(s)):
        b = f"layers_{i}/"
        for norm in ("input_layernorm", "post_attention_layernorm"):
            shapes.update({b + norm + "/scale": (d,), b + norm + "/bias": (d,)})
        shapes.update({b + "mlp_gate": (d, f), b + "mlp_up": (d, f), b + "mlp_down": (f, d)})
        if kind == "mamba":
            shapes.update({
                b + "in_proj": (d, 2 * E), b + "conv": (E, s["mamba_d_conv"]), b + "conv_bias": (E,),
                b + "x_proj": (E, R + 2 * N), b + "dt_proj": (R, E), b + "dt_bias": (E,),
                b + "A_log": (E, N), b + "D": (E,), b + "out_proj": (E, d)})
        elif kind == "gmu":
            shapes.update({b + "in_proj": (d, E), b + "out_proj": (E, d)})
        else:
            if kind == "cross":
                shapes[b + "Wq"] = (d, H * D)
            else:
                shapes[b + "Wqkv"] = (d, (H + 2 * KV) * D)
            shapes.update({b + name: (D,) for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")})
            shapes.update({b + "subln": (2 * D,), b + "out_proj": (H * D, d)})
    return shapes


@functools.lru_cache(maxsize=4)
def _maker(shapes, dt_min, dt_max, dt_floor):
    """One jitted call that draws every leaf from a key."""

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes):
            k = jax.random.fold_in(key, i)
            leaf = name.rsplit("/", 1)[-1]
            if leaf in ("scale", "subln", "D"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif leaf in ("bias", "conv_bias"):
                out[name] = jnp.zeros(shape, jnp.float32)
            elif leaf == "conv":
                # deviation 1/sqrt(taps): the sum over the taps keeps its input's scale
                out[name] = shape[1] ** -0.5 * jax.random.normal(k, shape, jnp.float32)
            elif leaf == "A_log":
                # Mamba-1's own: A[c] = -(1, 2, ..., N) in every channel
                out[name] = jnp.broadcast_to(
                    jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
            elif leaf == "dt_bias":
                # the inverse softplus of a step drawn log-uniformly in
                # [dt_min, dt_max] and floored at dt_floor
                u = jax.random.uniform(k, shape, jnp.float32)
                dt = jnp.exp(u * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
                dt = jnp.maximum(dt, dt_floor)
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif leaf == "dt_proj":
                # Mamba-1's own: uniform within dt_rank ** -0.5
                bound = shape[0] ** -0.5
                out[name] = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
            elif leaf.startswith("lambda_"):
                out[name] = 0.1 * jax.random.normal(k, shape, jnp.float32)
            else:
                # the source's 0.02, the tied table too
                out[name] = 0.02 * jax.random.normal(k, shape, jnp.float32)
        return out

    return make


def init_params(seed, cfg):
    s = _spec(cfg)
    make = _maker(tuple(sorted(param_shapes(cfg).items())), float(s.get("time_step_min", 0.001)),
                  float(s.get("time_step_max", 0.1)), float(s.get("time_step_floor", 1e-4)))
    return make(jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)), 7919))


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _norm(x, scale, bias, eps):
    """LayerNorm, recomputed in the backward pass (no product of matrices)."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _layer_norm(x, p, name, eps):
    return _norm(x, p[name + "/scale"], p[name + "/bias"], eps)


def _recurrence(u, delta, A, B, C, D):
    """The selective scan of one sequence, position by position: u and delta
    [T, E], A [E, N], B and C [T, N], D [E] -> y [T, E]."""
    T, E = u.shape

    def position(h, at):
        u_t, d_t, B_t, C_t = at
        h = jnp.exp(d_t[:, None] * A) * h + (d_t * u_t)[:, None] * B_t[None, :]
        return h, jnp.sum(h * C_t[None, :], axis=-1) + D * u_t

    @jax.checkpoint
    def block(h, ats):
        return jax.lax.scan(position, h, ats)

    size = next(b for b in range(min(BLOCK, T), 0, -1) if T % b == 0)
    blocks = jax.tree_util.tree_map(
        lambda a: a.reshape(T // size, size, *a.shape[1:]), (u, delta, B, C))
    _, y = jax.lax.scan(block, jnp.zeros((E, A.shape[1]), u.dtype), blocks)
    return y.reshape(T, E)


@jax.checkpoint
def _conv(u, w, bias):
    """SiLU(causal depthwise conv(u) + bias) on u [B, T, E], w [E, L]. No
    product of matrices is in here: recomputed in the backward pass."""
    L, T = w.shape[1], u.shape[1]
    padded = jnp.concatenate([jnp.zeros((u.shape[0], L - 1, u.shape[2]), u.dtype), u], axis=1)
    return jax.nn.silu(sum(w[None, None, :, j] * padded[:, j:j + T] for j in range(L)) + bias)


@jax.checkpoint
def _scan(u, dt, dt_bias, A_log, B, C, D):
    """The step, the decays and the recurrence on [B, T, E]: no product of
    matrices, recomputed in the backward pass, not kept."""
    delta = jax.nn.softplus(dt + dt_bias)
    return jax.vmap(_recurrence, in_axes=(0, 0, None, 0, 0, None))(u, delta, -jnp.exp(A_log), B, C, D)


def _mamba(n, p, b, s, ops):
    """The Mamba-1 mixer on n [B, T, d]: (its output, the memory y)."""
    _, E, N, R = _sizes(s)
    u, z = jnp.split(ops.dot(n, p[b + "in_proj"]), 2, axis=-1)
    u = _conv(u, p[b + "conv"], p[b + "conv_bias"])
    dt, B, C = jnp.split(ops.dot(u, p[b + "x_proj"]), [R, R + N], axis=-1)
    y = _scan(u, ops.dot(dt, p[b + "dt_proj"]), p[b + "dt_bias"], p[b + "A_log"], B, C, p[b + "D"])
    return ops.dot(_gate(z, y), p[b + "out_proj"]), y


def _pairs(q, k1, k2, v, window, lam, scale, lam_init, ops):
    """The differential pairs' outputs, normed: q [B, T, H, D] (its halves
    q1, q2 on k1, k2 [B, T, G, D]; query head i of a half on key head
    i // (H / 2 / G)), v [B, T, G, V]; causal and within ``window``. Written
    out ``QUERIES_AT_A_TIME`` queries a block against the keys the mask
    leaves them, each block (both maps, their difference and the norm) a
    ``jax.checkpoint``."""
    B, T, H, D = q.shape
    G, h = k1.shape[2], H // 2
    size = next(m for m in range(min(QUERIES_AT_A_TIME, T), 0, -1) if T % m == 0)

    @functools.partial(jax.checkpoint, static_argnums=(6, 7))
    def block(qb, kb1, kb2, vb, lam, scale, lo, first):
        rows = jnp.arange(lo, lo + qb.shape[1])[:, None]
        cols = jnp.arange(first, first + kb1.shape[1])[None, :]
        keep = cols <= rows
        if window:
            keep = keep & (rows - cols < window)
        # both maps at once: m = 0 reads q1 on k1, m = 1 q2 on k2
        qm = qb.reshape(B, qb.shape[1], 2, G, h // G, D)
        km = jnp.stack([kb1, kb2], axis=2)
        scores = ops.einsum("bqmgjd,bkmgd->bmgjqk", qm, km) / math.sqrt(D)
        a = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
        maps = ops.einsum("bmgjqk,bkgv->mbqgjv", a, vb).reshape(2, B, qb.shape[1], h, vb.shape[-1])
        o = maps[0] - lam * maps[1]
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + 1e-5)
        return o * scale * (1.0 - lam_init)

    out = []
    for lo in range(0, T, size):
        hi = lo + size
        first = max(0, lo - window + 1) if window else 0
        out.append(block(q[:, lo:hi], k1[:, first:hi], k2[:, first:hi], v[:, first:hi],
                         lam, scale, lo, first))
    return jnp.concatenate(out, axis=1)


def _differential(n, p, b, s, ops, l, kind, kv):
    """Differential attention on n [B, T, d]: (its output, its k1, k2, V)."""
    B, T, d = n.shape
    H, KV, D = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    if kind == "cross":
        q = ops.dot(n, p[b + "Wq"]).reshape(B, T, H, D)
        k1, k2, v = kv
    else:
        q, k, v = jnp.split(ops.dot(n, p[b + "Wqkv"]), [H * D, (H + KV) * D], axis=-1)
        q, k, v = q.reshape(B, T, H, D), k.reshape(B, T, KV, D), v.reshape(B, T, KV, D)
        k1, k2 = k[:, :, :KV // 2], k[:, :, KV // 2:]
        v = jnp.concatenate([v[:, :, :KV // 2], v[:, :, KV // 2:]], axis=-1)
    window = int(s["sliding_window"]) if kind == "window" else 0
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * l)
    lam = (jnp.exp(jnp.sum(p[b + "lambda_q1"] * p[b + "lambda_k1"]))
           - jnp.exp(jnp.sum(p[b + "lambda_q2"] * p[b + "lambda_k2"])) + lam_init)
    o = _pairs(q, k1, k2, v, window, lam, p[b + "subln"], lam_init, ops)
    return ops.dot(o.reshape(B, T, H * D), p[b + "out_proj"]), (k1, k2, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mlp(ops, n, w_gate, w_up, w_down):
    """(SiLU(n W_gate) * n W_up) W_down, its operands through the control's
    quantiser as ``ops.dot`` puts them (a straight-through gradient)."""
    q = ops._q
    return jnp.dot(q(_gate(jnp.dot(q(n), q(w_gate)), jnp.dot(q(n), q(w_up)))), q(w_down))


def _mlp_fwd(ops, n, w_gate, w_up, w_down):
    """Between the passes the MLP keeps ``n`` alone and computes ``n W_gate``
    and ``n W_up`` again in the backward pass (section Memory above)."""
    return _mlp(ops, n, w_gate, w_up, w_down), (n, w_gate, w_up, w_down)


def _mlp_bwd(ops, res, dy):
    # the barrier ties the two products to the backward pass's cotangent:
    # XLA would otherwise merge them with the forward's and keep those
    n, w_gate, w_up, w_down, dy = jax.lax.optimization_barrier((*res, dy))
    q = ops._q
    qn = q(n)
    gate, up = jnp.dot(qn, q(w_gate)), jnp.dot(qn, q(w_up))
    silu, sig = jax.nn.silu(gate), jax.nn.sigmoid(gate)
    dh = jnp.dot(dy, q(w_down).T)
    d_gate = dh * up * (sig * (1.0 + gate * (1.0 - sig)))
    d_up = dh * silu
    flat = lambda v: v.reshape(-1, v.shape[-1])
    return (jnp.dot(d_gate, q(w_gate).T) + jnp.dot(d_up, q(w_up).T),
            jnp.dot(flat(qn).T, flat(d_gate)), jnp.dot(flat(qn).T, flat(d_up)),
            jnp.dot(flat(q(silu * up)).T, flat(dy)))


_mlp.defvjp(_mlp_fwd, _mlp_bwd)


@jax.checkpoint
def _gate(gate, up):
    """SiLU(gate) * up: recomputed in the backward pass, not kept (a
    [T, intermediate] float32 array a layer); no product of matrices."""
    return jax.nn.silu(gate) * up


def _layer(x, p, carried, b, *, s, ops, l, kind, keeps, eps):
    """One layer on the residual stream x, and what the layers after it read
    (``memory``, ``kv``) as ``carried``."""
    n = _layer_norm(x, p, b + "input_layernorm", eps)
    if kind == "mamba":
        a, y = _mamba(n, p, b, s, ops)
        carried = dict(carried, memory=y) if keeps else carried
    elif kind == "gmu":
        a = ops.dot(jax.nn.silu(ops.dot(n, p[b + "in_proj"])) * carried["memory"], p[b + "out_proj"])
    else:
        a, mine = _differential(n, p, b, s, ops, l, kind, carried.get("kv"))
        carried = dict(carried, kv=mine) if keeps else carried
    x = x + a
    n = _layer_norm(x, p, b + "post_attention_layernorm", eps)
    return x + _mlp(ops, n, p[b + "mlp_gate"], p[b + "mlp_up"], p[b + "mlp_down"]), carried


def logits_fn(p, tokens, ops, cfg):
    s = _spec(cfg)
    eps = float(s["layer_norm_eps"])
    x = p["embed_tokens/embedding"][tokens]
    carried = {}
    for i, (l, kind, keeps) in enumerate(_layers(s)):
        x, carried = _layer(x, p, carried, f"layers_{i}/", s=s, ops=ops, l=l, kind=kind,
                            keeps=keeps, eps=eps)
    x = _layer_norm(x, p, "norm", eps)
    return ops.einsum("btd,vd->btv", x, p["embed_tokens/embedding"])


def unit_batch(cfg):
    """Shapes of one real document, for the FLOP count."""
    T = _spec(cfg)["length"]
    return (
        jax.ShapeDtypeStruct((1, T), jnp.int32),
        jax.ShapeDtypeStruct((1, T), jnp.int32),
    )
