"""Rotate-half rotary position embedding as one operator: q (or k) is read
once and written once in each direction.

``rotary(x, cos, sin)`` turns each head of x ``[B, T, H, D]`` by the angles
of ``cos`` / ``sin`` ``[T, R]`` (each angle in both halves of ``R``, a scale
such as YaRN's folded into both, as ``models/decoder.rotary_tables`` makes
them): the first ``R`` dims of a head become ``r * cos + concat(-r[R/2:],
r[:R/2]) * sin`` (``r = x[..., :R]``), products and sum in float32, rounded
once to x's dtype; the other ``D - R`` dims pass unchanged (partial rotary,
HF's ``partial_rotary_factor``: ``cat(rotated, x[..., R:])``). ``R = D`` turns
the whole head.

The kernel works on the ``[B, T, H·D]`` view — what the ``qkv`` products
write and the attention kernels read (``ops/flash_attention.py``), so
nothing is transposed or re-tiled around the call — in blocks of whole
rows of lane tiles. Inside a 128-lane tile the partner of a lane is a lane
rotation away: by 64 where a head fills the tile, by ``half`` one way or
the other where several heads share it or only the first ``R`` dims of a
head turn (two rotations and a select on the lane index: lane ``i`` pairs
with ``i + R/2`` where ``i mod D < R/2``, with ``i - R/2`` elsewhere). The
rotated half's sign lives in the ``sin`` table, which the wrapper lays out
as one lane tile ``[T, 128]``, with cos 1 and sin 0 on the lanes that pass
(``x * 1 + partner * 0`` is ``x`` to the bit); the table's block is indexed
by the rows alone, so it stays in VMEM while the grid walks the batch and
the heads.

The backward of a rotation is the rotation by the opposite angle: with the
sign folded into ``sin``, ``dx = g * cos + partner(g) * (-sin)`` — the same
kernel on the negated table (``rotary_bwd``). The rule keeps the two tables
and nothing of x. The tables are constants of the positions: the operator
gives them no gradient.

``takes_kernel`` is the decision between the kernel and the plain form, a
pure function of the shapes (no option, no model name, no backend: off the
TPU the kernel runs interpreted, which the tests use). The training shapes
of the cells that turn q and k this way qualify (T = 2 048 with heads of
128, T = 4 096 with heads of 64, T = 4 096 with heads of 128 turned whole
or on their first 64 dims); their 64-token evaluation documents and
the small shapes of the CPU tests take the plain form, which autodiff
differentiates."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.flash_attention import LANES, _use_interpret

ROWS = 256  # positions a block holds at least: T is a whole number of them
_BLOCK_BYTES = 2 * 1024 * 1024  # of x a grid step: far over a step's fixed cost


def takes_kernel(T: int, H: int, D: int, R: Optional[int] = None) -> bool:
    """Whether rotary over ``T`` positions of ``H`` heads of ``D``, the first
    ``R`` (default all ``D``) of each turned, goes to the kernel: ``T`` is a
    whole number of row blocks, heads fill a lane tile or share it evenly,
    all heads together make whole tiles, and ``R`` is even and no wider than
    a head."""
    R = D if R is None else R
    return (T % ROWS == 0 and D % 2 == 0 and LANES % D == 0 and (H * D) % LANES == 0
            and R % 2 == 0 and 0 < R <= D)


def plain(x, cos, sin):
    """The plain form, on x [B, T, H, D] and tables [T, R]."""
    R = cos.shape[-1]
    if R < x.shape[-1]:
        return jnp.concatenate([plain(x[..., :R], cos, sin), x[..., R:]], axis=-1)
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[None, :, None, :] + rotated * sin[None, :, None, :]).astype(x.dtype)


def rotary(x, cos, sin):
    """Rotate-half rotary on the first ``R`` dims of each head of x [B, T,
    H, D] (tables [T, R]), in float32, back in x's dtype."""
    B, T, H, D = x.shape
    R = cos.shape[-1]
    if not takes_kernel(T, H, D, R):
        return plain(x, cos, sin)
    # one lane tile of each table, the rotated half's sign in sin's; the
    # lanes that pass turn by cos 1, sin 0
    sign = jnp.where(jnp.arange(R) < R // 2, -1.0, 1.0)
    sin = sin * sign
    if R < D:
        cos = jnp.concatenate([cos, jnp.ones((T, D - R), cos.dtype)], axis=-1)
        sin = jnp.concatenate([sin, jnp.zeros((T, D - R), sin.dtype)], axis=-1)
    cos, sin = (jnp.tile(t, (1, LANES // D)) for t in (cos, sin))
    return _turn(x.reshape(B, T, H * D), cos, sin, (D, R)).reshape(x.shape)


def _kernel(x_ref, cos_ref, sin_ref, o_ref, *, head_dim, rotated):
    cos, sin = cos_ref[...], sin_ref[...]
    half = rotated // 2
    if 2 * half < LANES:
        lower = jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1) % head_dim < half
    for tile in range(x_ref.shape[-1] // LANES):
        lanes = slice(tile * LANES, (tile + 1) * LANES)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        partner = pltpu.roll(x, half, 1)  # lane i takes lane i - half
        if 2 * half < LANES:
            partner = jnp.where(lower, pltpu.roll(x, LANES - half, 1), partner)
        o_ref[0, :, lanes] = (x * cos + partner * sin).astype(o_ref.dtype)


def _blocks(T: int, width: int, itemsize: int):
    """(rows, lanes) of a block of x [T, width]: the widest run of whole
    lane tiles that divides ``width`` and keeps ``ROWS`` rows of it within
    ``_BLOCK_BYTES``, then as many rows as that leaves room for."""
    fits = lambda rows, lanes: rows * lanes * itemsize <= _BLOCK_BYTES
    lanes = max(n for n in range(LANES, width + 1, LANES) if width % n == 0 and fits(ROWS, n))
    rows = max(n for n in range(ROWS, T + 1, ROWS) if T % n == 0 and fits(n, lanes))
    return rows, lanes


# jitted: the layers' calls of one shape share one trace and one lowering
# (16 calls a step in Mellum's round program, of four shapes)
@functools.partial(jax.jit, static_argnames=("dims", "name", "interpret"))
def _call(x, cos, sin, dims, name, interpret):
    B, T, width = x.shape
    rows, lanes = _blocks(T, width, x.dtype.itemsize)
    # rows outermost: a table's block stays where it is while the grid walks
    # the batch and the lanes beneath it
    x_spec = pl.BlockSpec((1, rows, lanes), lambda t, b, w: (b, t, w))
    table_spec = pl.BlockSpec((rows, LANES), lambda t, b, w: (t, 0))
    return pl.pallas_call(
        functools.partial(_kernel, head_dim=dims[0], rotated=dims[1]),
        grid=(T // rows, B, width // lanes),
        in_specs=[x_spec, table_spec, table_spec],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name=name,
    )(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _turn(x, cos, sin, dims):
    return _call(x, cos, sin, dims, "rotary_fwd", _use_interpret())


def _turn_fwd(x, cos, sin, dims):
    return _call(x, cos, sin, dims, "rotary_fwd", _use_interpret()), (cos, sin)


def _turn_bwd(dims, tables, g):
    cos, sin = tables
    dx = _call(g, cos, -sin, dims, "rotary_bwd", _use_interpret())
    return dx, jnp.zeros_like(cos), jnp.zeros_like(sin)


_turn.defvjp(_turn_fwd, _turn_bwd)
