"""Max-pool over windows that tile the input, with its gradient written out.

``flax.linen.max_pool`` lowers to ``reduce_window(max)``, whose transpose
XLA emits as ``select_and_scatter``: a windowed scan that on a v5e wrote
the FEMNIST CNN's first pool gradient (``f32[20,28,28,32]``) in 20.3 µs a
step and the second in 5.1 µs, 16 % of the round program (PERF.md §6,
PR 33). Where the window equals its stride the windows do not overlap:
every input element belongs to one window, and the gradient is ``dy`` at
the window's first maximum and zero elsewhere — a compare and a select per
element.

The forward value stays ``reduce_window`` (bit-equal to ``nn.max_pool``).
The backward walks each window in row-major order exactly as
``select_and_scatter`` does with its ``>=`` select (keep the current
choice while it is ``>=`` the candidate, so the FIRST maximum wins a tie),
on the view ``[..., H/wh, wh, W/ww, ww, C]``. That view splits H and W,
which the chip's layout for these activations (``{3,0,2,1}``: channels on
the lanes, the batch on the sublanes, H and W major) turns into address
arithmetic: the window's first-maximum index is one small fused pass over
``x`` at the pooled resolution, and the gradient one compare-and-select
whose broadcasts of that index and of ``dy`` fuse into it (2.2 µs and
under 1 µs a step for the first pool).

The ``optimization_barrier`` on the gradient is what keeps it one pass.
Without it XLA fuses the select into each of its consumers (the ReLU
backward in front of the convolution's weight and bias gradients), moves
the reshape onto the select's operands and then has to materialise both
broadcasts at the full resolution: 12.5 µs a step instead of 3 (PERF.md
§6, PR 33 has the table of forms timed on the chip).

Pure JAX: CPU-safe, vmap-safe, any float dtype.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def max_pool(x, window_shape, strides=None):
    """``nn.max_pool(x, window_shape, strides=window_shape)`` for inputs
    ``[..., H, W, C]`` that the window tiles: the same value and the same
    gradient to the last bit, ties included. ``strides`` may be left out
    or repeat the window; a window that overlaps, leaves gaps or does not
    divide H and W raises (use ``nn.max_pool`` there)."""
    window = tuple(int(w) for w in window_shape)
    strides = window if strides is None else tuple(int(s) for s in strides)
    if len(window) != 2 or strides != window:
        raise ValueError(
            f"max_pool takes a 2-D window equal to its strides, got window "
            f"{window} with strides {strides}: use flax.linen.max_pool"
        )
    if x.ndim < 3 or x.shape[-3] % window[0] or x.shape[-2] % window[1]:
        raise ValueError(
            f"window {window} does not tile an input of shape {x.shape} "
            "([..., H, W, C]): use flax.linen.max_pool"
        )
    return _tile_max_pool(x, window)


def _reduce_window_max(x, window):
    with jax.named_scope("max_pool"):
        dims = (1,) * (x.ndim - 3) + window + (1,)
        return lax.reduce_window(x, -jnp.inf, lax.max, dims, dims, "VALID")


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _tile_max_pool(x, window):
    return _reduce_window_max(x, window)


def _tile_max_pool_fwd(x, window):
    return _reduce_window_max(x, window), x


def _tile_max_pool_bwd(window, x, dy):
    wh, ww = window
    *lead, H, W, C = x.shape
    with jax.named_scope("max_pool"):
        tiles = x.reshape(*lead, H // wh, wh, W // ww, ww, C)
        # select_and_scatter's walk over the window, at the pooled resolution
        best = tiles[..., 0:1, :, 0:1, :]
        first = jnp.zeros(best.shape, jnp.int32)
        for k in range(1, wh * ww):
            i, j = divmod(k, ww)
            candidate = tiles[..., i:i + 1, :, j:j + 1, :]
            take = ~(best >= candidate)
            best = jnp.where(take, candidate, best)
            first = jnp.where(take, k, first)
        place = (wh, 1, ww, 1)
        index = (lax.broadcasted_iota(jnp.int32, place, 0) * ww
                 + lax.broadcasted_iota(jnp.int32, place, 2))
        dx = jnp.where(first == index, dy[..., :, None, :, None, :],
                       jnp.zeros((), dy.dtype))
        # one pass that writes dx once; see the module docstring
        return (lax.optimization_barrier(dx).reshape(x.shape),)


_tile_max_pool.defvjp(_tile_max_pool_fwd, _tile_max_pool_bwd)
