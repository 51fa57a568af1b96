"""``ops/ssd.ssd`` (the chunked state-space scan, a ``jax.checkpoint``, and
at shapes ``takes_kernel`` admits the Pallas kernels ``ssd_fwd`` / ``ssd_bwd``,
interpreted here) and its gradient in every operand against ``jax.grad`` of
the recurrence written position by position, in float32 and bfloat16
operands, under ``vmap`` and inside a ``scan``, at lengths equal to, under
and not a multiple of the chunk; the kernels against the chunked products
too; causality and the state's reach; the kernels' shape rule; and the
second short convolution (``ops/short_conv.silu_short_conv``: a bias and a
SiLU, no gate) against plain numpy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import ssd as op
from fedml_tpu.ops.short_conv import silu_short_conv
from fedml_tpu.ops.ssd import ssd

NAMES = ("x", "dt", "A", "B", "C", "D")
CHUNK = 16


def recurrence(x, dt, A, B, C, D):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D
    x_t``, one position at a time, float32 throughout: x [T, H, P]."""
    x, dt, A, B, C, D = (v.astype(jnp.float32) for v in (x, dt, A, B, C, D))
    H, P = x.shape[1:]
    G, N = B.shape[1:]

    def position(S, at):
        x_t, dt_t, B_t, C_t = at
        B_h, C_h = jnp.repeat(B_t, H // G, axis=0), jnp.repeat(C_t, H // G, axis=0)
        S = jnp.exp(dt_t * A)[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * B_h[:, None]
        return S, jnp.sum(S * C_h[:, None], axis=-1) + D[:, None] * x_t

    return jax.lax.scan(position, jnp.zeros((H, P, N)), (x, dt, B, C))[1]


def plain(x, dt, A, B, C, D, chunk=None):
    fn = recurrence
    for _ in x.shape[:-3]:
        fn = jax.vmap(fn, in_axes=(0, 0, None, 0, 0, None))
    return fn(x, dt, A, B, C, D)


def case(lead, T, H, P, G, N, dtype, seed=0):
    """Operands as the mixer hands them over: x, B, C in the compute dtype;
    the step (after its softplus, between 1e-3 and about 1), A (-1 .. -16)
    and D float32."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], lead + (T, H, P), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], lead + (T, H), jnp.float32) - 2.0)
    A = -jax.random.uniform(ks[2], (H,), jnp.float32, 1.0, 16.0)
    B = jax.random.normal(ks[3], lead + (T, G, N), jnp.float32).astype(dtype)
    C = jax.random.normal(ks[4], lead + (T, G, N), jnp.float32).astype(dtype)
    D = jax.random.normal(ks[5], (H,), jnp.float32)
    cot = jax.random.normal(ks[6], lead + (T, H, P), jnp.float32)
    return (x, dt, A, B, C, D), cot


def value_and_grads(fn, operands, cot, chunk=CHUNK):
    def loss(*operands):
        y = fn(*operands, chunk)
        return jnp.sum(y.astype(jnp.float32) * cot), y

    (_, y), grads = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)), has_aux=True))(
        *operands)
    return (y,) + grads


def close(got, want, tol, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.max(np.abs(want))), 1e-6)
    assert float(np.max(np.abs(got - want))) <= tol * scale, (
        what, float(np.max(np.abs(got - want))) / scale)


# float32: both sides are exact float32; the chunked form takes its sums in
# another order (products over a chunk's positions, differences of cumulative
# sums under one exponential where the recurrence multiplies decays one by
# one): 5e-5 of the largest entry. bfloat16: the operands are the same rounded
# numbers on both sides and the decays float32 on both, but the chunked form
# rounds each product's float32 factor (the masked scores, dt x, the entering
# state) to bfloat16 once, 2^-9 relative each, where the recurrence keeps
# them float32, and rounds y once: 3e-2 of the largest entry.
TOLERANCE = {jnp.float32: 5e-5, jnp.bfloat16: 3e-2}


def chunked(x, dt, A, B, C, D, chunk):
    """The chunked products whatever the shape: what ``ssd`` runs where the
    kernels do not take the call."""
    *lead, T, H, P = x.shape
    G, N = B.shape[-2:]
    y = jax.checkpoint(op._chunked, static_argnums=6)(
        x.reshape(-1, T, H, P), dt.reshape(-1, T, H), A,
        B.astype(x.dtype).reshape(-1, T, G, N), C.astype(x.dtype).reshape(-1, T, G, N), D, chunk)
    return y.reshape(*lead, T, H, P)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("lead,T,H,P,G,N,chunk", [
    ((2,), 16, 4, 8, 2, 8, CHUNK),     # one chunk exactly
    ((1,), 48, 4, 8, 2, 8, CHUNK),     # three chunks
    ((2,), 5, 4, 8, 2, 8, CHUNK),      # under the chunk: one chunk of 5
    ((1,), 37, 4, 8, 4, 8, CHUNK),     # no multiple of the chunk: padded; a group a head
    ((), 32, 6, 4, 1, 16, CHUNK),      # no leading axis; one group for all heads
    ((2, 2), 20, 4, 8, 2, 8, CHUNK),   # two leading axes
    # the kernels: two chunks of 128, two heads of 64 a group sharing a lane tile
    ((), 256, 4, 64, 2, 128, 128),
    ((2,), 256, 4, 64, 2, 128, 128),
    # a head a lane tile, four groups, three chunks
    ((1,), 384, 4, 128, 4, 128, 128),
], ids=["one_chunk", "three_chunks", "under_the_chunk", "no_multiple", "no_lead", "two_leads",
        "kernels_no_lead", "kernels_lead", "kernels_head_a_tile"])
def test_value_and_every_gradient_match_the_recurrence(lead, T, H, P, G, N, chunk, dtype):
    operands, cot = case(lead, T, H, P, G, N, dtype)
    got = value_and_grads(ssd, operands, cot, chunk)
    want = value_and_grads(plain, operands, cot)
    assert got[0].dtype == dtype and got[0].shape == lead + (T, H, P)
    for name, a, b, operand in zip(("y",) + tuple("d_" + n for n in NAMES), got, want,
                                   (operands[0],) + operands):
        assert a.shape == b.shape and (name == "y" or a.dtype == operand.dtype), name
        close(a, b, TOLERANCE[dtype], (name, lead, T))
    if op.takes_kernel(T, H, P, G, N, chunk):
        # and the kernels against the products they stand in for, which round
        # at the same places: only the order of the sums differs
        assert "pallas_call" in str(jax.make_jaxpr(lambda *a: ssd(*a, chunk))(*operands))
        for name, a, b in zip(("y",) + NAMES, got, value_and_grads(chunked, operands, cot, chunk)):
            close(a, b, TOLERANCE[dtype], (name, "chunked", lead, T))


def test_the_chunk_changes_no_number_beyond_the_order_of_sums():
    operands, _ = case((1,), 64, 4, 8, 2, 8, jnp.float32)
    want = plain(*operands)
    for chunk in (4, 16, 64, 128):
        close(jax.jit(ssd, static_argnums=6)(*operands, chunk), want, 5e-5, chunk)


def test_under_vmap_with_weights_of_its_own_and_inside_a_scan():
    """The client ``vmap`` batches A and D too (every client's own leaves);
    the local-step scan carries them."""
    operands, cot = case((2,), 24, 4, 8, 2, 8, jnp.float32)
    x, dt, A, B, C, D = operands
    As, Ds = jnp.stack([A, 0.5 * A, 2 * A]), jnp.stack([D, -D, 0 * D])
    xs = jnp.stack([x, 2 * x, -x])

    def loss(fn):
        def one(x, A, D):
            return jnp.sum(fn(x, dt, A, B, C, D, CHUNK) * cot)
        return jax.jit(jax.vmap(jax.value_and_grad(one, argnums=(0, 1, 2))))(xs, As, Ds)

    (lg, gg), (lw, gw) = loss(ssd), loss(plain)
    close(lg, lw, 5e-5, "vmap loss")
    for a, b in zip(gg, gw):
        close(a, b, 5e-5, "vmap grads")

    def steps(fn):
        def step(carry, x_t):
            A, total = carry
            value, dA = jax.value_and_grad(
                lambda A: jnp.sum(fn(x_t, dt, A, B, C, D, CHUNK) * cot))(A)
            return (A - 1e-3 * dA, total + value), value
        return jax.jit(lambda: jax.lax.scan(step, (A, 0.0), xs))()

    (A_g, total_g), values_g = steps(ssd)
    (A_w, total_w), values_w = steps(plain)
    close(values_g, values_w, 5e-5, "scan values")
    close(A_g, A_w, 5e-5, "scan carry")


def test_causal_and_the_state_reaches_past_the_chunk():
    """Changing token t leaves every output before t as it was (to the bit:
    the chunked form computes earlier chunks from earlier operands alone, and
    inside the chunk the mask is exact), and changes outputs more than a
    chunk later, which only the state carries there."""
    (x, dt, A, B, C, D), _ = case((1,), 80, 4, 8, 2, 8, jnp.float32)
    A = -jnp.full((4,), 0.05)                 # slow decay: the state lives long
    fn = jax.jit(lambda x, B: ssd(x, dt, A, B, C, D, CHUNK))
    base = fn(x, B)
    t = 21
    for moved in (fn(x.at[0, t].add(1.0), B), fn(x, B.at[0, t].add(1.0))):
        assert np.array_equal(np.asarray(moved[0, :t]), np.asarray(base[0, :t]))
        late = np.abs(np.asarray(moved[0, t + 2 * CHUNK:]) - np.asarray(base[0, t + 2 * CHUNK:]))
        assert float(np.max(np.abs(np.asarray(moved[0, t]) - np.asarray(base[0, t])))) > 1e-3
        assert float(late.max()) > 1e-4


def test_the_kernels_under_vmap_with_weights_of_their_own_and_inside_a_scan():
    """The client ``vmap`` around both kernels (each client's own A and D:
    the batched ``pallas_call`` takes them as operands of their own) and the
    local-step scan around their gradient, against the chunked products."""
    operands, cot = case((1,), 256, 4, 64, 2, 128, jnp.float32)
    x, dt, A, B, C, D = operands
    assert op.takes_kernel(256, 4, 64, 2, 128, 128)
    As, Ds, xs = jnp.stack([A, 0.5 * A]), jnp.stack([D, -D]), jnp.stack([x, -2 * x])

    def clients(fn):
        def one(x, A, D):
            return jnp.sum(fn(x, dt, A, B, C, D, 128) * cot)
        return jax.jit(jax.vmap(jax.value_and_grad(one, argnums=(0, 1, 2))))(xs, As, Ds)

    (lk, gk), (lc, gc) = clients(ssd), clients(chunked)
    close(lk, lc, 5e-5, "vmap loss")
    for a, b in zip(gk, gc):
        close(a, b, 5e-5, "vmap grads")

    def steps(fn):
        def step(A, x_t):
            value, dA = jax.value_and_grad(lambda A: jnp.sum(fn(x_t, dt, A, B, C, D, 128) * cot))(A)
            return A - 1e-3 * dA, value
        return jax.jit(lambda: jax.lax.scan(step, A, xs))()

    (A_k, values_k), (A_c, values_c) = steps(ssd), steps(chunked)
    close(values_k, values_c, 5e-5, "scan values")
    close(A_k, A_c, 5e-5, "scan carry")


def test_the_kernels_carry_the_state_across_chunks():
    """With a slow decay a token's input reaches outputs chunks later through
    the state the kernels carry, and its gradient reaches back the same way;
    outputs before it are untouched to the bit; all of it as the recurrence
    has it."""
    (x, dt, A, B, C, D), cot = case((1,), 384, 4, 64, 2, 128, jnp.float32)
    A = -jnp.full((4,), 0.05)
    fn = jax.jit(lambda x, B: ssd(x, dt, A, B, C, D, 128))
    base = fn(x, B)
    t = 100                                   # the first chunk: its state enters chunks 1 and 2
    for moved in (fn(x.at[0, t].add(1.0), B), fn(x, B.at[0, t].add(1.0))):
        assert np.array_equal(np.asarray(moved[0, :t]), np.asarray(base[0, :t]))
        late = np.abs(np.asarray(moved[0, 256:]) - np.asarray(base[0, 256:]))
        assert float(late.max()) > 1e-4
    close(base, plain(x, dt, A, B, C, D), 5e-5, "value")
    # the last chunk's cotangent alone: what reaches x in the first chunk went
    # through the state's gradient carried back over a whole chunk
    late_cot = cot.at[:, :256].set(0.0)
    dx = [jax.jit(jax.grad(lambda x: jnp.sum(fn_(x, dt, A, B, C, D) * late_cot)))(x)
          for fn_ in (lambda *a: ssd(*a, 128), plain)]
    assert float(jnp.max(jnp.abs(dx[0][0, :128]))) > 1e-4
    close(dx[0], dx[1], 5e-5, "gradient across chunks")


@pytest.mark.parametrize("shape,takes", [
    ((4096, 64, 64, 8, 128, 128), True),    # nemotron-twotower-30b-a3b.silo2t4k-ssm's training step
    ((64, 64, 64, 8, 128, 128), False),     # its 64-token evaluation: one chunk of 64
    ((4096, 64, 64, 8, 128, 64), False),    # another chunk
    ((4160, 64, 64, 8, 128, 128), False),   # no whole number of chunks
    ((4096, 64, 64, 8, 64, 128), False),    # a state under a lane tile
    ((4096, 6, 48, 2, 128, 128), False),    # a group's heads (3 of 48) no whole lane tiles
    ((4096, 8, 8, 2, 16, 128), False),      # the tests' and rehearsals' small widths
    ((4096, 64, 64, 6, 128, 128), False),   # groups that do not divide the heads
    ((32768, 64, 64, 8, 128, 128), False),  # ssd_bwd's chunk states over their fast memory
    ((256, 4, 64, 2, 128, 128), True),      # the interpreted cases above
    ((384, 4, 128, 4, 128, 128), True),
])
def test_the_kernels_take_whole_chunks_of_128_in_whole_lane_tiles(shape, takes):
    assert op.takes_kernel(*shape) is takes
    T, H, P, G, N, chunk = shape
    if T <= 4096 and H % G == 0:
        operands, _ = case((1,), T, H, P, G, N, jnp.bfloat16)
        jaxpr = str(jax.make_jaxpr(lambda *a: ssd(*a, chunk))(*operands))
        assert ("pallas_call" in jaxpr) is takes


def test_shapes_that_do_not_belong_together_are_refused():
    (x, dt, A, B, C, D), _ = case((1,), 16, 4, 8, 2, 8, jnp.float32)
    with pytest.raises(ValueError, match="groups do not divide"):
        ssd(x, dt, A, B[..., :1, :].repeat(3, -2), C[..., :1, :].repeat(3, -2), D)
    with pytest.raises(ValueError, match="takes dt"):
        ssd(x, dt[..., :3], A, B, C, D)


# --- the second short convolution ---------------------------------------------


def numpy_conv(x, w, bias):
    """``SiLU(sum_j w[:, j] x_{t-(L-1)+j} + b)`` in float64, zeros before
    position 0."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    T, L = x.shape[-2], w.shape[1]
    out = np.zeros_like(x)
    for t in range(T):
        for j in range(L):
            s = t - (L - 1) + j
            if s >= 0:
                out[..., t, :] += w[:, j] * x[..., s, :]
    if bias is not None:
        out = out + np.asarray(bias, np.float64)
    return out / (1.0 + np.exp(-out))


@pytest.mark.parametrize("shape,d,L,biased", [
    ((2, 19), 8, 4, True), ((1, 2), 4, 4, True), ((7,), 8, 3, False), ((2, 3, 9), 4, 2, True),
], ids=["four_taps", "shorter_than_the_filter", "no_bias_no_lead", "two_leads"])
def test_the_biased_activated_convolution_against_plain_numpy(shape, d, L, biased):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(ks[0], shape + (d,))
    w = L ** -0.5 * jax.random.normal(ks[1], (d, L))
    b = 0.3 * jax.random.normal(ks[2], (d,)) if biased else None
    got = jax.jit(silu_short_conv)(x, w, b)
    close(got, numpy_conv(x, w, b), 1e-5, "value")
    # the gradient of a checkpoint, against autodiff of the same sum written plainly
    def plain_conv(x, w, b):
        pad = [(0, 0)] * x.ndim
        pad[-2] = (L - 1, 0)
        padded = jnp.pad(x, pad)
        c = sum(w[:, j] * padded[..., j:j + x.shape[-2], :] for j in range(L))
        return jax.nn.silu(c if b is None else c + b)
    args = (x, w) if b is None else (x, w, b)
    grads = [jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a, *([None] * (3 - len(a)))))),
                              argnums=tuple(range(len(args)))))(*args)
             for fn in (silu_short_conv, plain_conv)]
    for a, b_ in zip(*grads):
        close(a, b_, 1e-5, "gradient")
    # bfloat16 operands: float32 sums, one rounding of the result
    low = jax.jit(silu_short_conv)(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), b)
    assert low.dtype == jnp.bfloat16
    close(low, numpy_conv(x.astype(jnp.bfloat16).astype(jnp.float32),
                          w.astype(jnp.bfloat16).astype(jnp.float32), b), 8e-3, "bfloat16")
