"""``ops/short_conv.gated_short_conv`` and its gradient (the function is a
``jax.checkpoint``) against ``jax.grad`` of the operator written plainly (the sum over the shifted copies
of ``u = B * X``, gated by ``C``), in float32 and bfloat16, under ``vmap``
and inside a ``scan``, at lengths that are a multiple of nothing and shorter
than the filter; and causality."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops.short_conv import gated_short_conv


def plain(bcx, w):
    """The equations, float32 throughout, no checkpoint: L shifted
    copies of ``u`` out of one zero-padded array."""
    d, L = w.shape
    T = bcx.shape[-2]
    b, c, x = (bcx[..., i * d:(i + 1) * d].astype(jnp.float32) for i in range(3))
    u = b * x
    pad = [(0, 0)] * u.ndim
    pad[-2] = (L - 1, 0)
    padded = jnp.pad(u, pad)
    conv = sum(w[:, j].astype(jnp.float32) * padded[..., j:j + T, :] for j in range(L))
    return c * conv


def case(shape, d, L, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    bcx = jax.random.normal(ks[0], shape + (3 * d,), jnp.float32).astype(dtype)
    w = (L ** -0.5 * jax.random.normal(ks[1], (d, L), jnp.float32)).astype(dtype)
    cot = jax.random.normal(ks[2], shape + (d,), jnp.float32)
    return bcx, w, cot


def value_and_grads(fn, bcx, w, cot):
    def loss(bcx, w):
        y = fn(bcx, w)
        return jnp.sum(y.astype(jnp.float32) * cot), y

    (_, y), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(bcx, w)
    return (y,) + grads


def close(got, want, tol, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = max(float(np.max(np.abs(want))), 1e-6)
    assert float(np.max(np.abs(got - want))) <= tol * scale, what


# float32: both sides are exact float32 and differ by the order of a few sums
# (the filter's gradient sums batch x time terms): 1e-5 of the largest entry.
# bfloat16: the operands are the same rounded numbers on both sides and the
# sums are float32 on both; the results are rounded to bfloat16 once (2^-8
# relative, 4e-3) and the plain side's gradients are rounded by autodiff's
# casts at other places than the operator's: 2e-2.
TOLERANCE = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape,d,L", [
    ((2, 37), 8, 3),     # a length that is a multiple of nothing
    ((1, 2), 4, 3),      # shorter than the filter
    ((3, 1), 4, 3),      # one position: only the last tap sees anything
    ((2, 16), 8, 4),     # another filter length
    ((5,), 8, 3),        # no batch axis
    ((2, 3, 11), 4, 2),  # two leading axes
    ((2, 9), 8, 1),      # one tap: a gate and a scale
])
def test_value_and_both_gradients_match_the_plain_form(shape, d, L, dtype):
    bcx, w, cot = case(shape, d, L, dtype)
    got = value_and_grads(gated_short_conv, bcx, w, cot)
    want = value_and_grads(plain, bcx, w, cot)
    assert got[0].dtype == dtype and got[1].dtype == dtype and got[2].dtype == dtype
    assert got[0].shape == shape + (d,) and got[1].shape == bcx.shape and got[2].shape == w.shape
    for name, a, b in zip(("y", "d_bcx", "d_w"), got, want):
        close(a, b, TOLERANCE[dtype], (name, shape, L))


@pytest.mark.parametrize("filter_batched", [True, False], ids=["filters_per_client", "one_filter"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_under_vmap_every_member_is_its_own(dtype, filter_batched):
    """The clients of a round: stacked inputs, and stacked filters once
    local training has made them differ (``in_axes`` 0) or the global one
    (``None``). Each member's value and gradients are those of a call of
    its own."""
    K, d, L = 3, 8, 3
    bcx, _, cot = case((K, 2, 13), d, L, dtype)
    ws = jnp.stack([case((1, 1), d, L, dtype, seed=s)[1] for s in range(K)])
    axes = (0, 0 if filter_batched else None, 0)
    w_in = ws if filter_batched else ws[0]
    got = jax.jit(jax.vmap(
        lambda b, w, c: value_and_grads(gated_short_conv, b, w, c), in_axes=axes))(bcx, w_in, cot)
    for i in range(K):
        want = value_and_grads(plain, bcx[i], ws[i] if filter_batched else ws[0], cot[i])
        for name, a, b in zip(("y", "d_bcx", "d_w"), got, want):
            close(a[i], b, TOLERANCE[dtype], (name, i))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_inside_a_scan_the_gradient_is_the_unrolled_one(dtype):
    """The local steps of a client: the filter is the scan's carry, updated
    by its own gradient each step, as SGD does."""
    d, L, steps = 8, 3, 4
    batches, w0, cots = case((steps, 2, 10), d, L, dtype)

    def run(fn):
        def step(w, xs):
            bcx, cot = xs
            g = jax.grad(lambda w: jnp.sum(fn(bcx, w).astype(jnp.float32) * cot))(w)
            return (w - 0.1 * g.astype(w.dtype)).astype(w.dtype), g

        return jax.jit(lambda: jax.lax.scan(step, w0, (batches, cots)))()

    (w_got, g_got), (w_want, g_want) = run(gated_short_conv), run(plain)
    close(g_got, g_want, 2 * TOLERANCE[dtype], "gradients")
    close(w_got, w_want, 2 * TOLERANCE[dtype], "carry")


@pytest.mark.parametrize("t", [0, 5, 16])
def test_changing_a_token_leaves_every_earlier_output_as_it_was(t):
    """Causality, to the bit: outputs before position t do not read it, the
    outputs at t .. t + L - 1 do, and nothing later does either."""
    d, L, T = 8, 3, 17
    bcx, w, _ = case((2, T), d, L, jnp.float32)
    moved = bcx.at[:, t].add(1.0)
    conv = jax.jit(gated_short_conv)
    before, after = conv(bcx, w), conv(moved, w)
    assert jnp.array_equal(before[:, :t], after[:, :t])
    assert not jnp.array_equal(before[:, t], after[:, t])
    assert jnp.array_equal(before[:, t + L:], after[:, t + L:])
    # and the gradient flows the other way: a cotangent at t reaches only
    # the inputs at t - (L - 1) .. t
    cot = jnp.zeros((2, T, d)).at[:, t].set(1.0)
    d_bcx = jax.jit(lambda bcx, w, cot: jax.vjp(gated_short_conv, bcx, w)[1](cot)[0])(bcx, w, cot)
    reached = np.flatnonzero(np.asarray(jnp.any(d_bcx != 0, axis=(0, 2))))
    assert reached.min() >= max(0, t - (L - 1)) and reached.max() == t


def test_a_last_axis_that_is_not_three_filters_wide_is_refused():
    with pytest.raises(ValueError, match="last axis"):
        gated_short_conv(jnp.zeros((2, 4, 25)), jnp.zeros((8, 3)))


def test_only_the_projection_and_the_filter_are_kept_between_the_passes():
    """The checkpoint's residuals are the two arguments: autodiff of the
    plain form would keep float32 arrays of the output's size."""
    bcx, w, _ = case((2, 12), 8, 3, jnp.bfloat16)
    _, pull = jax.vjp(gated_short_conv, bcx, w)
    kept = sorted((leaf.shape, str(leaf.dtype)) for leaf in jax.tree_util.tree_leaves(pull))
    assert kept == sorted([(bcx.shape, "bfloat16"), (w.shape, "bfloat16")])
