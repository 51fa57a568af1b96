"""The gradient of a table lookup as a Pallas kernel that adds the step's
rows into the table block by block in fast memory, onto the head's gradient
where the table is also the head.

    lookup(table [V, d], ids [...] int) -> (rows [..., d], head [V, d])

``rows`` is ``jnp.take(table, ids, axis=0)``, ``nn.Embed``'s own forward, bit
for bit, and ``head`` is ``table`` itself, for a tied head to read (an
untied model leaves it unread, and its gradient ``dhead`` is then zeros).
The gradient of the table is ``dhead`` plus what the transpose of ``take``
computes from ``ids`` and the rows' gradient ``dx`` [..., d]: row ``v`` of
the transpose is the sum of the rows of ``dx`` whose id is ``v``, by
``take``'s rules (a negative id counts from the end, every other id outside
``[0, V)`` adds nothing).

XLA computes that transpose as a scatter-add over the sorted ids, one row
at a time. Where the table is also the head, the scatter adds in place into
the head's weight-gradient product in HBM, and a DMA moves whole (16, 128)
tiles of a bfloat16 array, never one row: each of a step's 4 096 updates is
then a serial read-modify-write of a tile row of the table (10.2 ms a step
for 25 008 x 2 560, against 0.18 ms of bytes); into a zero table of
16 384 x 2 048 it takes 1.0 ms alone on a v5e, the kernel 0.36 ms. This
kernel (``table_grad`` in the compiled program), per call:

1. sorts the ids once (a stable sort: equal ids keep the order of their
   rows), and finds where each block of ``BLOCK`` table rows starts among
   them; both go to SMEM by scalar prefetch;
2. loads ``dx`` into VMEM once, by one DMA;
3. walks the table ``BLOCK`` rows at a time: widens the block of ``dhead``
   to float32, adds the rows whose id falls in it in sorted order (one row
   of ``dx`` read from VMEM, widened to float32 and added to its table row),
   and writes the block once, in the table's dtype, over ``dhead``'s (the
   two share their buffer). A bfloat16 ``dx`` is read as 32-bit words that
   hold two rows each, and the row's half is moved to the high half of a
   float32, which is the widening (as ``ops/slot_sum.py`` reads its table).

So each row's gradient is float32, ``dhead``'s value first and then the
rows' in the order of their rows, rounded once: for a float32 table that is
the arithmetic of ``dhead`` plus the transpose added in that order, for a
bfloat16 one a single rounding where the scatter rounds at each add. Handing
``dhead`` to the kernel keeps the head's product the op before it, as the
scatter had it, so that no second table-sized gradient is planned.

:func:`takes_kernel` is the decision between the kernel and ``take``'s own
transpose, made from the shapes alone for the v5e, whose VMEM
``_VMEM_BUDGET`` is drawn from; off the TPU the kernel runs interpreted,
which the tests use."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.flash_attention import LANES, _use_interpret

BLOCK = 512  # table rows a grid step widens, adds into and writes
_SUBLANES = 16  # rows of a bfloat16 tile: dx is padded to whole ones
_VMEM_BUDGET = 100 * 1024 * 1024  # of the v5e's 128 MiB, for dx and the blocks
_MAX_ROWS = 16384  # ids a call takes: their order and rows sit in SMEM
_NO_ROW = jnp.iinfo(jnp.int32).max  # the sort key of an id that adds nothing


def _block(V: int) -> int:
    """Table rows a grid step takes: ``BLOCK``, or the whole table where it
    is shorter."""
    return BLOCK if V > BLOCK else V


def _vmem_bytes(V: int, T: int, d: int, itemsize: int) -> int:
    """Fast memory of a call with ``dx`` and the table in ``itemsize``:
    ``dx``, a float32 block and two blocks each of ``dhead`` and of the
    gradient."""
    rows = -(-T // _SUBLANES) * _SUBLANES
    return rows * d * itemsize + _block(V) * d * (4 + 4 * itemsize)


def takes_kernel(V: int, d: int, T: int, itemsize: int = 4) -> bool:
    """Whether the gradient of a lookup of ``T`` ids in a table of ``V`` rows
    of ``d`` goes to the kernel: rows of whole lane tiles, the ids' order
    and rows within SMEM, and ``dx`` with the blocks within
    ``_VMEM_BUDGET`` at ``itemsize`` (float32's by default, the widest the
    kernel takes)."""
    return (d % LANES == 0 and 0 < T <= _MAX_ROWS
            and _vmem_bytes(V, T, d, itemsize) <= _VMEM_BUDGET)


def _kernel(bounds_ref, order_ref, rows_ref, dx_hbm, head_ref, out_ref, dx, sem, *acc):
    b = pl.program_id(0)
    # the float32 sums: the gradient itself, or a block of them where it is narrower
    acc = acc[0] if acc else out_ref
    block = acc.shape[0]

    @pl.when(b == 0)
    def _():
        # the grid's steps run in order and dx stays for all of them
        copy = pltpu.make_async_copy(dx_hbm, dx, sem)
        copy.start()
        copy.wait()

    acc[...] = head_ref[...].astype(jnp.float32)
    packed = dx.dtype.itemsize == 2
    words = dx.bitcast(jnp.uint32) if packed else dx
    base = b * block

    def add(j, carry):
        p = order_ref[j]
        row = words[pl.ds(p >> 1 if packed else p, 1), :]
        if packed:
            # a bfloat16's bits are the high half of the float32 of equal value;
            # row 2q is the word's low half, row 2q + 1 its high half
            shift = (16 - 16 * (p & 1)).astype(jnp.uint32)
            row = pltpu.bitcast((row << shift) & jnp.uint32(0xFFFF0000), jnp.float32)
        at = pl.ds(rows_ref[j] - base, 1)
        acc[at, :] = acc[at, :] + row
        return carry

    jax.lax.fori_loop(bounds_ref[b], bounds_ref[b + 1], add, 0)
    if acc is not out_ref:
        out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _table_grad(ids, dx, head, interpret: bool):
    T, d = dx.shape
    V = head.shape[0]
    block = _block(V)
    blocks = -(-V // block)
    # take's rules: a negative id counts from the end; what is still outside
    # [0, V) sorts after every block and adds nothing
    ids = jnp.where(ids < 0, ids + V, ids)
    key = jnp.where((ids >= 0) & (ids < V), ids, _NO_ROW)
    rows, order = jax.lax.sort((key, jnp.arange(T, dtype=jnp.int32)), num_keys=1,
                               is_stable=True)
    bounds = jnp.searchsorted(rows, jnp.arange(blocks + 1, dtype=jnp.int32) * block,
                              method="compare_all").astype(jnp.int32)
    padded = -(-T // _SUBLANES) * _SUBLANES
    if padded != T:
        dx = jnp.pad(dx, ((0, padded - T), (0, 0)))
    item = dx.dtype.itemsize
    spec = pl.BlockSpec((block, d), lambda b, *_: (b, 0))
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((V, d), head.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(blocks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), spec],
            out_specs=spec,
            scratch_shapes=[pltpu.VMEM((padded, d), dx.dtype), pltpu.SemaphoreType.DMA(())] + (
                [] if head.dtype == jnp.float32 else [pltpu.VMEM((block, d), jnp.float32)]),
        ),
        # the gradient is written over dhead's buffer (operand 4: the three
        # prefetched ones count)
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(V, T, d, max(item, head.dtype.itemsize)) + (16 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=T * d, transcendentals=0,
            bytes_accessed=T * d * item + 2 * V * d * head.dtype.itemsize + T * 8),
        interpret=interpret,
        name="table_grad",
    )(bounds, order, rows, dx, head)


def _take_transpose(ids, dx, head):
    """``head`` plus ``take``'s transpose of ``dx``, in float32 and rounded
    once: the kernel's arithmetic but for the order of its sums."""
    wide = jax.vjp(lambda t: jnp.take(t, ids, axis=0),
                   jnp.zeros(head.shape, jnp.float32))[1](dx.astype(jnp.float32))[0]
    return (head.astype(jnp.float32) + wide).astype(head.dtype)


@functools.lru_cache(maxsize=None)
def _batchable(interpret: bool):
    """``_table_grad`` with a rule of its own for a batch of tables (a
    clients' vmap): ``pallas_call``'s would stack the calls' results in a
    loop, which XLA fuses into the kernel, dropping its VMEM limit. The rule
    stacks the tables instead, one above the other (each table's ids moved
    down by the rows above it), and makes one call where :func:`takes_kernel`
    holds for the stack at its dtypes; else each table takes ``take``'s
    transpose."""

    @jax.custom_batching.custom_vmap
    def grad(ids, dx, head):
        return _table_grad(ids, dx, head, interpret)

    @grad.def_vmap
    def _(size, batched, ids, dx, head):
        ids, dx, head = (a if lead else jnp.broadcast_to(a, (size,) + a.shape)
                         for a, lead in zip((ids, dx, head), batched))
        (T, d), V = dx.shape[1:], head.shape[1]
        if not takes_kernel(size * V, d, size * T, max(dx.dtype.itemsize, head.dtype.itemsize)):
            return jax.vmap(_take_transpose)(ids, dx, head), True
        ids = jnp.where(ids < 0, ids + V, ids)
        ids = jnp.where((ids >= 0) & (ids < V),
                        ids + V * jnp.arange(size, dtype=jnp.int32)[:, None], size * V)
        out = grad(ids.reshape(-1), dx.reshape(-1, d), head.reshape(-1, d))
        return out.reshape(size, V, d), True

    return grad


def table_grad(ids, dx, head):
    """``[V, d]`` in ``head``'s dtype: row ``v`` the float32 sum of
    ``head[v]`` and then the rows of ``dx`` [..., d] whose id in ``ids``
    [...] is ``v`` (by ``take``'s rules), in their order, rounded once.
    ``dx`` and ``head`` are bfloat16 or float32."""
    for name, a in (("dx", dx), ("head", head)):
        if a.dtype not in (jnp.bfloat16, jnp.float32):
            raise ValueError(f"table_grad takes a bfloat16 or float32 {name}, got {a.dtype}")
    d = dx.shape[-1]
    return _batchable(_use_interpret())(
        ids.reshape(-1).astype(jnp.int32), dx.reshape(-1, d), head)


@jax.custom_vjp
def _lookup(table, ids):
    return jnp.take(table, ids, axis=0), table


def _lookup_fwd(table, ids):
    return (jnp.take(table, ids, axis=0), table), ids


def _lookup_bwd(ids, cotangents):
    dx, dhead = cotangents
    return table_grad(ids, dx, dhead), None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def lookup(table, ids):
    """``(jnp.take(table, ids, axis=0), table)``: the rows, and the table
    for a tied head to read. Where :func:`takes_kernel` holds for the table
    and ``ids``' count, the table's gradient is :func:`table_grad`'s, the
    rows' gradient added onto the head's (zeros where nothing reads it);
    else autodiff's."""
    V, d = table.shape
    if takes_kernel(V, d, ids.size) and table.dtype in (jnp.bfloat16, jnp.float32):
        return _lookup(table, ids)
    return jnp.take(table, ids, axis=0), table
