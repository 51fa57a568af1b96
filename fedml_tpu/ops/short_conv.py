"""The gated short convolution: the token mixer of a layer without attention.

    [B, C, X] = bcx                       three equal parts of the last axis
    u   = B * X
    c_t = sum_{j < L} w[:, j] * u_{t-(L-1)+j}        u zero before position 0
    y   = C * c

A causal depthwise convolution (one filter of ``L`` taps a channel, no bias)
between two gates: no activation, no softmax, no positions. ``bcx`` is the
input projection's output ``n W_in`` and ``y`` goes to the output projection;
both products stay with the caller.

The core is bound by bandwidth. Written as ``L`` shifted multiply-adds, XLA
fuses the split, both gates and the taps into one elementwise pass that
reads ``bcx`` and writes ``y``: 4 x width numbers a token. The function is a
``jax.checkpoint``: nothing but ``bcx`` and the filter is kept between the
passes, and the backward pass recomputes ``u``
and ``c`` (two multiplies and ``L`` multiply-adds a number, against a
[.., T, d] float32 residual each that autodiff would keep), reads ``dy`` and
``bcx`` and writes the three gradients, 7 x width numbers a token, plus the
filter's gradient, which sums over every axis but the channels.

Precision: ``u``, the sums over the taps and the filter's gradient are
float32 whatever dtype the operands arrive in (bfloat16 in the training
cells); the results are rounded to the operands' dtypes once.

Pure ``jax.numpy``: any leading axes, any length (shorter than the filter
too), and it batches and scans like any elementwise function, which is what
the client ``vmap`` and ``scan`` schedules need."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _past(x, k: int):
    """``x_{t-k}``: ``x`` moved ``k`` positions along the time axis (-2),
    zeros moving in."""
    pad = [(0, 0)] * x.ndim
    pad[-2] = (k, 0)
    return jnp.pad(x, pad)[..., :x.shape[-2], :]


@jax.checkpoint
def gated_short_conv(bcx, w):
    """bcx [..., T, 3d] (``B | C | X``), w [d, L] -> y [..., T, d] in bcx's
    dtype; the equations at the top of this file."""
    d, L = w.shape
    if bcx.shape[-1] != 3 * d:
        raise ValueError(
            f"gated_short_conv: a filter of {d} channels takes a last axis of {3 * d}, "
            f"got {bcx.shape}")
    b, c, x = (bcx[..., i * d:(i + 1) * d].astype(jnp.float32) for i in range(3))
    w32, u = w.astype(jnp.float32), b * x
    # tap j reads L-1-j positions back
    conv = w32[:, L - 1] * u
    for j in range(L - 1):
        conv = conv + w32[:, j] * _past(u, L - 1 - j)
    return (c * conv).astype(bcx.dtype)
