"""The sum over a token's slots of the table rows they read, as a Pallas
kernel that reads only the rows of live slots.

    slot_sum(table [R, d], readers [N, top_k] int32, weights [N, top_k] | None)
        -> [N, d] float32

Row ``n`` of the result is the float32 sum over ``k`` of
``table[readers[n, k]]`` (times ``weights[n, k]``), ``k = 0`` first; a
reader equal to ``R`` is an empty slot and adds nothing. It is the same
function as ``models/decoder.sum_readers``, the XLA form it stands in for,
and the same arithmetic: each row is widened to float32 and multiplied by
its weight in float32, and a token's live slots are added in slot order.
``sum_readers`` adds an empty slot's zero, which changes no sum but the sign
of a zero, so the two agree in value to the last bit.

The routed experts' token side calls it twice a layer (``weighted_rows``
forward, the backward of the dispatch gather), with the rows of the held
experts' (token, slot) pairs as the table: each live row is read by exactly
one slot, the rows that hold a pair come first, and only 6-25 % of the slots
are live in the expert cells. ``sum_readers`` pays for a row read at every
slot; this kernel, per call:

1. loads the table's rows up to the last one a live slot reads into fast
   memory (VMEM), ``ROW_CHUNK`` rows a DMA: what the slots read, once, with
   no row of an empty slot. A DMA from HBM moves whole (8, 128) tiles of the
   table's layout, so one row cannot be fetched alone; from VMEM it can.
2. walks the tokens ``TOKEN_TILE`` at a time, eight to a block of sublanes,
   and of each token only its live slots (prefetched as one int32 a token:
   their count, then their indices). A live slot costs one load of its row,
   broadcast over the sublanes, and the widening, multiply and add into its
   token's sublane. A bfloat16 table is read as 32-bit words that hold two
   rows each, and the row's half is moved to the high half of a float32,
   which is the widening.

The readers and weights of a tile sit in SMEM; the sums are float32 in VMEM
and written once a tile, in the result's dtype.

:func:`takes_kernel` is the decision between the kernel and ``sum_readers``,
made from the shapes alone (no option, no model name) for the v5e, whose
128 MiB of VMEM ``_VMEM_BUDGET`` is drawn from; off the TPU the kernel runs
interpreted, which the tests use. The kernel's work follows the slots that
name a row, on average half the table's rows (the callers' row bound is
twice the even share), plus a fixed cost a token; ``sum_readers``' follows
the N x top_k slots. Alone on one v5e, with readers from a routing sort, the
kernel was the faster at 6 and 8 slots a token where the table's rows were
an eighth, a quarter and three eighths of the slots (by 11-25 % at three
eighths) and the slower at half (by 6-13 %), and at 4 slots it was the
slower at a quarter already (PERF.md section 6): the rule admits at most
three eighths and at least 6 slots. Every expert cell's training step
qualifies but ``lfm2-8b-a1b.silo2t4k``'s (8 192 rows for 4 096 x 4 slots);
the CPU tests' and rehearsals' widths, whose rows are no whole lane tile,
and the evaluation's tables, which would not fit VMEM, keep
``sum_readers``. Tables are bfloat16 or float32."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.flash_attention import LANES, _use_interpret

TOKEN_TILE = 512  # tokens a grid step takes
ROW_CHUNK = 128  # table rows a DMA loads
_SMEM_TILE = 1024  # int32s a tile of a one-dimensional array in SMEM holds
_VMEM_BUDGET = 100 * 1024 * 1024  # of the v5e's 128 MiB, for the table and the results
_SUBLANES = 8
# a token's live slots packed into one int32: their count, then 3 bits each
_COUNT_BITS, _SLOT_BITS = 4, 3
_MAX_SLOTS = 8


def _tile(N: int) -> int:
    """Tokens a grid step takes: ``TOKEN_TILE``, or all ``N`` in whole
    sublane blocks where they are fewer; the last tile is padded with empty
    slots."""
    return min(TOKEN_TILE, -(-N // _SUBLANES) * _SUBLANES)


def _vmem_bytes(R: int, d: int, itemsize: int, tile: int) -> int:
    """Fast memory of a call: the table, a tile's float32 sums and two
    blocks of results."""
    return R * d * itemsize + 3 * tile * d * 4


def takes_kernel(N: int, top_k: int, d: int, R: int) -> bool:
    """Whether a slot sum of ``N`` tokens of ``top_k`` slots over a table of
    ``R`` rows of ``d`` goes to the kernel: at least 6 slots a token and
    the table's rows at most three eighths of the slots, rows of whole lane
    tiles, the table in whole DMA chunks, a tile's readers in whole SMEM
    tiles (or one tile for all tokens), and the table with the results
    within ``_VMEM_BUDGET`` in
    float32, the widest table the kernel takes (the widest cell's, 8 192 x
    2 304, needs 81 MiB so; 45 in its bfloat16)."""
    tile = _tile(N)
    return (8 * R <= 3 * N * top_k and 6 <= top_k <= _MAX_SLOTS and d % LANES == 0
            and R % ROW_CHUNK == 0
            and (tile >= N or tile * top_k % _SMEM_TILE == 0)
            and _vmem_bytes(R, d, 4, tile) <= _VMEM_BUDGET)


def _kernel(chunks_ref, slots_ref, readers_ref, *refs, R: int, top_k: int, chunk: int,
            weighted: bool):
    weights_ref = refs[0] if weighted else None
    table_hbm, out_ref, table, sem, *sums = refs[1:] if weighted else refs
    # the float32 sums: the result itself, or a tile of them where it is narrower
    sums = sums[0] if sums else out_ref
    t = pl.program_id(0)
    tile, d = out_ref.shape

    def load(c):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        return pltpu.make_async_copy(table_hbm.at[rows], table.at[rows], sem)

    @pl.when(t == 0)
    def _():
        # the grid's steps run in order and the table stays for all of them
        chunks = chunks_ref[0]
        jax.lax.fori_loop(0, chunks, lambda c, x: (load(c).start(), x)[1], 0)
        jax.lax.fori_loop(0, chunks, lambda c, x: (load(0).wait(), x)[1], 0)

    packed = table.dtype.itemsize == 2
    words = table.bitcast(jnp.uint32) if packed else table
    sublane = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, d), 0)
    sums[...] = jnp.zeros(sums.shape, jnp.float32)

    def add(n, at, k):
        """Adds slot ``k`` of token ``n`` (sublane ``at`` of its block) to
        the token's sum: the row, broadcast over the block's sublanes,
        widened, weighted and added in the token's sublane."""
        j = n * top_k + k
        r = readers_ref[j]
        q = r >> 1 if packed else r  # the word row: rows 2q (low half) and 2q + 1
        row = jnp.broadcast_to(words[pl.ds(q, 1), :], (_SUBLANES, d))
        if packed:
            # a bfloat16's bits are the high half of the float32 of equal value
            shift = (16 - 16 * (r & 1)).astype(jnp.uint32)
            row = pltpu.bitcast((row << shift) & jnp.uint32(0xFFFF0000), jnp.float32)
        if weighted:
            row = row * weights_ref[j]
        rows = pl.ds(pl.multiple_of(n - at, _SUBLANES), _SUBLANES)
        total = sums[rows, :]
        sums[rows, :] = jnp.where(sublane == at, total + row, total)

    def block(b, carry):
        for at in range(_SUBLANES):
            n = b * _SUBLANES + at
            live = slots_ref[t * tile + n]  # the count, then 3 bits a live slot

            def slot(i, c, n=n, at=at, live=live):
                add(n, at, (live >> (_COUNT_BITS + _SLOT_BITS * i)) & ((1 << _SLOT_BITS) - 1))
                return c

            jax.lax.fori_loop(0, live & ((1 << _COUNT_BITS) - 1), slot, 0)
        return carry

    jax.lax.fori_loop(0, tile // _SUBLANES, block, 0)
    if sums is not out_ref:
        out_ref[...] = sums[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def _slot_sum(table, readers, weights, out_dtype, interpret: bool):
    R, d = table.shape
    N, top_k = readers.shape
    tile = _tile(N)
    padded = -(-N // tile) * tile
    if padded != N:
        readers = jnp.pad(readers, ((0, padded - N), (0, 0)), constant_values=R)
        if weights is not None:
            weights = jnp.pad(weights, ((0, padded - N), (0, 0)))
    chunk = ROW_CHUNK if R % ROW_CHUNK == 0 else R
    live = readers < R
    # DMA chunks up to the last row a live slot reads; each token's live
    # slots in order, 3 bits each above their count
    chunks = -(-(jnp.max(jnp.where(live, readers, -1)) + 1) // chunk)
    before = jnp.cumsum(live, axis=1, dtype=jnp.int32) - live
    k = jnp.arange(top_k, dtype=jnp.int32)
    slots = jnp.sum(live, axis=1, dtype=jnp.int32) + jnp.sum(
        jnp.where(live, k << (_COUNT_BITS + _SLOT_BITS * before), 0), axis=1, dtype=jnp.int32)
    weighted = weights is not None
    smem = pl.BlockSpec((tile * top_k,), lambda t, c, s: (t,), memory_space=pltpu.SMEM)
    operands = [readers.reshape(-1)] + (
        [weights.astype(jnp.float32).reshape(-1)] if weighted else [])
    item = table.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, R=R, top_k=top_k, chunk=chunk, weighted=weighted),
        out_shape=jax.ShapeDtypeStruct((padded, d), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(padded // tile,),
            in_specs=[smem] * len(operands) + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, d), lambda t, c, s: (t, 0)),
            # an even number of rows: a bfloat16 table is read as words of two
            scratch_shapes=[pltpu.VMEM((-(-R // 2) * 2, d), table.dtype),
                            pltpu.SemaphoreType.DMA(())] + (
                [] if out_dtype == jnp.float32 else [pltpu.VMEM((tile, d), jnp.float32)]),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(R, d, item, tile) + (16 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * N * top_k * d, transcendentals=0,
            bytes_accessed=R * d * item + N * d * jnp.dtype(out_dtype).itemsize + N * top_k * 8),
        interpret=interpret,
        name="slot_sum",
    )(chunks.astype(jnp.int32).reshape(1), slots, *operands, table)[:N]


def slot_sum(table, readers, weights=None, out_dtype=jnp.float32):
    """``[N, d]``: token ``n``'s float32 sum over ``k`` of
    ``table[readers[n, k]]`` times ``weights[n, k]`` (1 where ``weights`` is
    None), ``k = 0`` first, rounded once to ``out_dtype``; a reader of
    ``len(table)`` is an empty slot. The table is bfloat16 or float32, and
    ``top_k`` at most 8."""
    if table.dtype not in (jnp.bfloat16, jnp.float32):
        raise ValueError(f"slot_sum takes a bfloat16 or float32 table, got {table.dtype}")
    if readers.shape[1] > _MAX_SLOTS:
        raise ValueError(f"slot_sum takes at most {_MAX_SLOTS} slots a token, got {readers.shape[1]}")
    return _slot_sum(table, readers.astype(jnp.int32), weights, jnp.dtype(out_dtype),
                     _use_interpret())
