"""``ops/ssd.ssd`` (the chunked state-space scan, a ``jax.checkpoint``) and
its gradient in every operand against ``jax.grad`` of the recurrence written
position by position, in float32 and bfloat16 operands, under ``vmap`` and
inside a ``scan``, at lengths equal to, under and not a multiple of the
chunk; causality and the state's reach; and the second short convolution
(``ops/short_conv.silu_short_conv``: a bias and a SiLU, no gate) against
plain numpy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("lead,T,H,P,G,N", [
    ((2,), 16, 4, 8, 2, 8),     # one chunk exactly
    ((1,), 48, 4, 8, 2, 8),     # three chunks
    ((2,), 5, 4, 8, 2, 8),      # under the chunk: one chunk of 5
    ((1,), 37, 4, 8, 4, 8),     # no multiple of the chunk: padded; a group a head
    ((), 32, 6, 4, 1, 16),      # no leading axis; one group for all heads
    ((2, 2), 20, 4, 8, 2, 8),   # two leading axes
], ids=["one_chunk", "three_chunks", "under_the_chunk", "no_multiple", "no_lead", "two_leads"])
def test_value_and_every_gradient_match_the_recurrence(lead, T, H, P, G, N, dtype):
    operands, cot = case(lead, T, H, P, G, N, dtype)
    got = value_and_grads(ssd, operands, cot)
    want = value_and_grads(plain, operands, cot)
    assert got[0].dtype == dtype and got[0].shape == lead + (T, H, P)
    for name, a, b, operand in zip(("y",) + tuple("d_" + n for n in NAMES), got, want,
                                   (operands[0],) + operands):
        assert a.shape == b.shape and (name == "y" or a.dtype == operand.dtype), name
        close(a, b, TOLERANCE[dtype], (name, lead, T))


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
