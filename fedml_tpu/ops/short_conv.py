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
the client ``vmap`` and ``scan`` schedules need.

The second convolution, :func:`silu_short_conv`, is the one ahead of a
state-space scan (a Mamba-2 mixer's ``conv1d`` over ``x | B | C``), with a
bias and an activation and no gate:

    c_t = sum_{j < L} w[:, j] * x_{t-(L-1)+j} + b        x zero before position 0
    y   = c * sigmoid(c)

the same taps over the same shifted copies (:func:`_taps`, ``_past``), the
sums, the bias and the SiLU in float32, one rounding to the operand's dtype,
and a ``jax.checkpoint`` for the same reason: only ``x``, the filter and the
bias are kept, 2 x width numbers a token forward and 3 x width backward."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _past(x, k: int):
    """``x_{t-k}``: ``x`` moved ``k`` positions along the time axis (-2),
    zeros moving in."""
    pad = [(0, 0)] * x.ndim
    pad[-2] = (k, 0)
    return jnp.pad(x, pad)[..., :x.shape[-2], :]


def _taps(u, w):
    """The causal depthwise sum over the filter's taps of float32 ``u``
    [..., T, d]: tap j of ``w`` [d, L] reads L-1-j positions back."""
    L = w.shape[1]
    w32 = w.astype(jnp.float32)
    conv = w32[:, L - 1] * u
    for j in range(L - 1):
        conv = conv + w32[:, j] * _past(u, L - 1 - j)
    return conv


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
    return (c * _taps(b * x, w)).astype(bcx.dtype)


@jax.checkpoint
def silu_short_conv(x, w, bias=None):
    """x [..., T, d], w [d, L], bias [d] or None -> SiLU(conv(x) + bias)
    [..., T, d] in x's dtype; the second convolution at the top of this file."""
    if x.shape[-1] != w.shape[0]:
        raise ValueError(
            f"silu_short_conv: a filter of {w.shape[0]} channels takes that last axis, "
            f"got {x.shape}")
    conv = _taps(x.astype(jnp.float32), w)
    if bias is not None:
        conv = conv + bias.astype(jnp.float32)
    return jax.nn.silu(conv).astype(x.dtype)
