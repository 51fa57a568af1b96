"""Rotate-half rotary position embedding as one operator: q (or k) is read
once and written once in each direction.

``rotary(x, cos, sin)`` turns each head of x ``[B, T, H, D]`` by the angles
of ``cos`` / ``sin`` ``[T, D]`` (each angle in both halves of ``D``, a scale
such as YaRN's folded into both, as ``models/decoder.rotary_tables`` makes
them): ``x * cos + concat(-x[half:], x[:half]) * sin``, products and sum in
float32, rounded once to x's dtype.

The kernel works on the ``[B, T, H·D]`` view — what the ``qkv`` products
write and the attention kernels read (``ops/flash_attention.py``), so
nothing is transposed or re-tiled around the call — in blocks of whole
rows of lane tiles. Inside a 128-lane tile the partner of a lane is a lane
rotation away: by 64 where a head fills the tile, by ``half`` one way or
the other where several heads share it (two rotations and a select on the
lane index). The rotated half's sign lives in the ``sin`` table, which the
wrapper lays out as one lane tile ``[T, 128]``; the table's block is
indexed by the rows alone, so it stays in VMEM while the grid walks the
batch and the heads.

The backward of a rotation is the rotation by the opposite angle: with the
sign folded into ``sin``, ``dx = g * cos + partner(g) * (-sin)`` — the same
kernel on the negated table (``rotary_bwd``). The rule keeps the two tables
and nothing of x. The tables are constants of the positions: the operator
gives them no gradient.

``takes_kernel`` is the decision between the kernel and the plain form, a
pure function of the shapes (no option, no model name, no backend: off the
TPU the kernel runs interpreted, which the tests use). The training shapes
of the cells that turn q and k this way qualify (T = 2 048 with heads of
128, T = 4 096 with heads of 64); their 64-token evaluation documents and
the small shapes of the CPU tests take the plain form, which autodiff
differentiates."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.flash_attention import LANES, _use_interpret

ROWS = 256  # positions a block holds at least: T is a whole number of them
_BLOCK_BYTES = 2 * 1024 * 1024  # of x a grid step: far over a step's fixed cost


def takes_kernel(T: int, H: int, D: int) -> bool:
    """Whether rotary over ``T`` positions of ``H`` heads of ``D`` goes to
    the kernel: ``T`` is a whole number of row blocks, heads fill a lane
    tile or share it evenly, and all heads together make whole tiles."""
    return T % ROWS == 0 and D % 2 == 0 and LANES % D == 0 and (H * D) % LANES == 0


def plain(x, cos, sin):
    """The plain form, on x [B, T, H, D]."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[None, :, None, :] + rotated * sin[None, :, None, :]).astype(x.dtype)


def rotary(x, cos, sin):
    """Rotate-half rotary on x [B, T, H, D], in float32, back in x's dtype."""
    B, T, H, D = x.shape
    if not takes_kernel(T, H, D):
        return plain(x, cos, sin)
    # one lane tile of each table, the rotated half's sign in sin's
    sign = jnp.where(jnp.arange(D) < D // 2, -1.0, 1.0)
    cos, sin = (jnp.tile(t, (1, LANES // D)) for t in (cos, sin * sign))
    return _turn(x.reshape(B, T, H * D), cos, sin, D).reshape(x.shape)


def _kernel(x_ref, cos_ref, sin_ref, o_ref, *, head_dim):
    cos, sin = cos_ref[...], sin_ref[...]
    half = head_dim // 2
    if head_dim < LANES:
        lower = jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1) % head_dim < half
    for tile in range(x_ref.shape[-1] // LANES):
        lanes = slice(tile * LANES, (tile + 1) * LANES)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        partner = pltpu.roll(x, half, 1)  # lane i takes lane i - half
        if head_dim < LANES:
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
@functools.partial(jax.jit, static_argnames=("head_dim", "name", "interpret"))
def _call(x, cos, sin, head_dim, name, interpret):
    B, T, width = x.shape
    rows, lanes = _blocks(T, width, x.dtype.itemsize)
    # rows outermost: a table's block stays where it is while the grid walks
    # the batch and the lanes beneath it
    x_spec = pl.BlockSpec((1, rows, lanes), lambda t, b, w: (b, t, w))
    table_spec = pl.BlockSpec((rows, LANES), lambda t, b, w: (t, 0))
    return pl.pallas_call(
        functools.partial(_kernel, head_dim=head_dim),
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
def _turn(x, cos, sin, head_dim):
    return _call(x, cos, sin, head_dim, "rotary_fwd", _use_interpret())


def _turn_fwd(x, cos, sin, head_dim):
    return _call(x, cos, sin, head_dim, "rotary_fwd", _use_interpret()), (cos, sin)


def _turn_bwd(head_dim, tables, g):
    cos, sin = tables
    dx = _call(g, cos, -sin, head_dim, "rotary_bwd", _use_interpret())
    return dx, jnp.zeros_like(cos), jnp.zeros_like(sin)


_turn.defvjp(_turn_fwd, _turn_bwd)
