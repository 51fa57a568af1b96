"""``ops/selective_scan.selective_scan`` (Mamba-1's scan: a decay a channel
and state element) in its two forms, the plain pass over positions and, at
shapes ``takes_kernel`` admits, the Pallas kernels ``sscan_fwd`` /
``sscan_bwd`` (interpreted here), against the recurrence written out in
float64 numpy and ``jax.grad`` of it position by position: the value and the
gradient of every operand, at a length of whole chunks and at one that is
not, with steps of extreme size, in float32 and bfloat16 operands, under the
clients' ``vmap`` with weights of its own and inside a ``scan``; causality
and the state's reach across chunks; the kernels' shape rule; and that the
tolerance catches states kept in bfloat16 and the backward kernel's lane
sums taken in bfloat16."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import selective_scan as op
from fedml_tpu.ops.selective_scan import selective_scan

NAMES = ("u", "dt", "A", "B", "C", "D", "delta_bias")


def case(lead, T, C, N, dtype, seed=0, dt_shift=0.0, sharp=False):
    """Operands as the mixer hands them over: u, dt, B, C in the compute
    dtype; A (-1 .. -N, moved by a little a channel), D and the step's bias
    float32; ``dt_shift`` moves every step before its softplus; ``sharp``
    scales B and C by a different power of ten at each position of a group
    of 16 (B by 10^(t mod 4 - 2), C by 10^(1 - t mod 3)), so that a sum over
    a group that mixes its positions up is far off."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    u = jax.random.normal(ks[0], lead + (T, C), jnp.float32).astype(dtype)
    dt = (jax.random.normal(ks[1], lead + (T, C), jnp.float32) + dt_shift).astype(dtype)
    A = -jnp.arange(1, N + 1, dtype=jnp.float32)[None, :] * jnp.exp(
        0.1 * jax.random.normal(ks[2], (C, N), jnp.float32))
    B = jax.random.normal(ks[3], lead + (T, N), jnp.float32)
    Cm = jax.random.normal(ks[4], lead + (T, N), jnp.float32)
    if sharp:
        t = jnp.arange(T)[:, None]
        B = B * 10.0 ** (t % 4 - 2)
        Cm = Cm * 10.0 ** (1 - t % 3)
    B, Cm = B.astype(dtype), Cm.astype(dtype)
    D = jax.random.normal(ks[5], (C,), jnp.float32)
    bias = jax.random.normal(ks[6], (C,), jnp.float32) - 2.0
    cot = jax.random.normal(ks[7], lead + (T, C), jnp.float32)
    return (u, dt, A, B, Cm, D, bias), cot


def recurrence(u, dt, A, B, C, D, bias):
    """The equations position by position, float32: u [T, C]."""
    u, dt, B, C = (v.astype(jnp.float32) for v in (u, dt, B, C))
    delta = jax.nn.softplus(dt + bias)

    def position(h, at):
        u_t, d_t, B_t, C_t = at
        h = jnp.exp(d_t[:, None] * A) * h + (d_t * u_t)[:, None] * B_t[None, :]
        return h, h @ C_t + D * u_t

    return jax.lax.scan(position, jnp.zeros(A.shape), (u, delta, B, C))[1]


def written_out(u, dt, A, B, C, D, bias):
    """The same in float64 numpy, one position and one channel at a time
    over the state: [T, C]."""
    u, dt, A, B, C, D, bias = (np.asarray(v, np.float64) for v in (u, dt, A, B, C, D, bias))
    x = dt + bias
    delta = np.maximum(x, 0) + np.log1p(np.exp(-np.abs(x)))
    h, out = np.zeros(A.shape), []
    for t in range(u.shape[0]):
        h = np.exp(delta[t][:, None] * A) * h + (delta[t] * u[t])[:, None] * B[t][None, :]
        out.append(h @ C[t] + D * u[t])
    return np.stack(out)


def lifted(fn, lead):
    for _ in lead:
        fn = jax.vmap(fn, in_axes=(0, 0, None, 0, 0, None, None))
    return fn


def value_and_grads(fn, operands, cot):
    def loss(*operands):
        y = fn(*operands)
        return jnp.sum(y.astype(jnp.float32) * cot), y

    (_, y), grads = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(7)), has_aux=True))(
        *operands)
    return (y,) + grads


def close(got, want, tol, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.max(np.abs(want))), 1e-6)
    assert float(np.max(np.abs(got - want))) <= tol * scale, (
        what, float(np.max(np.abs(got - want))) / scale)


# float32: both sides are exact float32 and differ by the order of the sums
# over the state and, in the gradients of A, D, B, C and the bias, over the
# positions: 2e-5 of the largest entry. bfloat16: the operands are the same
# rounded numbers on both sides and the states float32 on both; the results
# (y, du, ddt) are rounded to bfloat16 once, 2^-9 relative: 1e-2.
TOLERANCE = {jnp.float32: 2e-5, jnp.bfloat16: 1e-2}


def test_the_plain_form_is_the_recurrence_written_out():
    """float32 against float64 numpy, one sequence of 40 positions (one
    rematerialised block of them)."""
    (u, dt, A, B, C, D, bias), _ = case((), 40, 24, 8, jnp.float32)
    got = selective_scan(u, dt, A, B, C, D, bias)
    close(got, written_out(u, dt, A, B, C, D, bias), 1e-5, "y")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("lead,T,C,N,shift,sharp", [
    ((2,), 40, 24, 8, 0.0, False),          # the plain form: two sequences
    ((), 130, 16, 16, 0.0, False),          # the plain form: no multiple of its block of 128
    ((), 256, 512, 16, 0.0, False),         # the kernels: one chunk, one block each way
    ((1,), 296, 1024, 16, 0.0, False),      # two chunks, the second padded; two blocks
    ((), 256, 512, 16, 12.0, False),        # steps near 12: every decay under exp(-12)
    ((), 256, 512, 16, -12.0, False),       # steps near 6e-6: nearly nothing decays or enters
    ((), 552, 1536, 16, 0.0, False),        # three chunks, the third padded; three blocks
    ((), 256, 512, 16, 0.0, True),          # B and C a power of ten apart within a group
], ids=["plain", "plain_no_multiple", "kernels", "kernels_padded_two_blocks",
        "kernels_long_steps", "kernels_short_steps", "kernels_padded_three_chunks_three_blocks",
        "kernels_sharp_within_a_group"])
def test_value_and_every_gradient_match_the_recurrence(lead, T, C, N, shift, sharp, dtype):
    operands, cot = case(lead, T, C, N, dtype, dt_shift=shift, sharp=sharp)
    assert op.takes_kernel(T, C, N) is (T >= 256)
    got = value_and_grads(selective_scan, operands, cot)
    want = value_and_grads(lifted(recurrence, lead), operands, cot)
    assert got[0].dtype == operands[0].dtype
    for name, g, w in zip(("y",) + NAMES, got, want):
        assert g.shape == w.shape and (name == "y" or g.dtype == w.dtype), name
        close(g, w, TOLERANCE[dtype], name)


def test_under_vmap_with_weights_of_its_own_and_inside_a_scan():
    """The clients' ``vmap`` hands each client its own A, D and bias; the
    local steps' ``scan`` runs the kernels inside a loop: each client's
    value and gradients are the recurrence's."""
    clients, steps, T, C, N = 2, 2, 256, 512, 16
    (u, dt, A, B, Cm, D, bias), cot = case((clients, steps), T, C, N, jnp.float32)
    A = jnp.stack([A, 1.3 * A])
    D = jnp.stack([D, -D])
    bias = jnp.stack([bias, bias + 1.0])

    def client(fn):
        def one(u, dt, A, B, Cm, D, bias, cot):
            def step(acc, at):
                u, dt, B, Cm, cot = at
                y = fn(u, dt, A, B, Cm, D, bias)
                return acc + jnp.sum(y * cot), None
            return jax.lax.scan(step, 0.0, (u, dt, B, Cm, cot))[0]
        return jax.vmap(jax.value_and_grad(one, argnums=tuple(range(7))))

    got = jax.jit(client(selective_scan))(u, dt, A, B, Cm, D, bias, cot)
    want = jax.jit(client(recurrence))(u, dt, A, B, Cm, D, bias, cot)
    close(got[0], want[0], 2e-5, "loss")
    for name, g, w in zip(NAMES, got[1], want[1]):
        close(g, w, 2e-5, name)


def test_causal_and_the_state_reaches_past_the_chunk():
    """y at position t does not move when u after t does, and the first
    chunk's input reaches the second chunk's outputs through the state
    (small decays, steps near 1e-3 so that the state keeps it)."""
    (u, dt, A, B, C, D, bias), _ = case((), 512, 512, 16, jnp.float32, dt_shift=-5.0)
    A = A / 16.0
    y = selective_scan(u, dt, A, B, C, D, bias)
    moved = selective_scan(u.at[300:].add(1.0), dt, A, B, C, D, bias)
    assert jnp.array_equal(y[:300], moved[:300])
    early = selective_scan(u.at[:10].multiply(100.0), dt, A, B, C, D, bias)
    assert float(jnp.max(jnp.abs(early[300:] - y[300:]))) > 1e-3


@pytest.mark.parametrize("T,C,N,takes", [
    (4096, 5120, 16, True),    # the cell's training step
    (64, 5120, 16, False),     # its evaluation: the plain form
    (255, 5120, 16, False),    # under one chunk
    (257, 5120, 16, True),     # over one chunk: padded to two
    (4096, 5000, 16, False),   # channels in no whole block
    (4096, 5120, 12, False),   # a state in no whole sublane tile
    (4096, 5120, 256, False),  # a state over a lane tile
])
def test_the_kernels_take_whole_blocks_of_channels_and_a_state_of_sublane_tiles(T, C, N, takes):
    assert op.takes_kernel(T, C, N) is takes


def test_shapes_that_do_not_belong_together_are_refused():
    (u, dt, A, B, C, D, bias), _ = case((), 16, 8, 4, jnp.float32)
    with pytest.raises(ValueError, match="selective_scan"):
        selective_scan(u, dt[:8], A, B, C, D, bias)
    with pytest.raises(ValueError, match="selective_scan"):
        selective_scan(u, dt, A[:, :2], B, C, D, bias)


def test_the_tolerance_catches_states_kept_in_bfloat16(monkeypatch):
    """The plain form with its step, decays and states in bfloat16, where the
    operator states float32: the gap to the float64 recurrence is orders over
    the float32 tolerance."""
    (u, dt, A, B, C, D, bias), _ = case((), 40, 24, 8, jnp.float32)
    want = written_out(u, dt, A, B, C, D, bias)
    monkeypatch.setattr(op, "STATE_DTYPE", jnp.bfloat16)
    got = selective_scan(u, dt, A, B, C, D, bias)
    scale = float(np.max(np.abs(want)))
    gap = float(np.max(np.abs(np.asarray(got, np.float64) - want)))
    assert gap > 100 * TOLERANCE[jnp.float32] * scale


def test_the_tolerance_catches_a_lane_sum_taken_in_bfloat16(monkeypatch):
    """The backward kernel's lane sums (dB, dC: a group's products summed over
    the channels) taken as a single-pass bfloat16 product would take them,
    each product rounded to bfloat16 before float32 accumulation, where the
    kernel adds them in float32: dB and dC are then orders over the float32
    tolerance off the recurrence."""
    operands, cot = case((), 256, 512, 16, jnp.float32)
    want = value_and_grads(recurrence, operands, cot)
    exact = op._lane_sums

    def in_bfloat16(parts):
        return exact([p.astype(jnp.bfloat16).astype(jnp.float32) for p in parts])

    jax.clear_caches()   # the kernels' jitted traces hold the exact sums
    monkeypatch.setattr(op, "_lane_sums", in_bfloat16)
    try:
        got = value_and_grads(selective_scan, operands, cot)
    finally:
        jax.clear_caches()
    for name in ("B", "C"):
        at = 1 + NAMES.index(name)
        g, w = np.asarray(got[at]), np.asarray(want[at])
        gap = float(np.max(np.abs(g - w))) / float(np.max(np.abs(w)))
        assert gap > 10 * TOLERANCE[jnp.float32], (name, gap)
