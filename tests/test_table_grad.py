"""``ops/table_grad``: a tied lookup whose forward is ``take``'s and whose
table gradient is the Pallas kernel's, interpreted on the CPU, held to the
transpose of ``jnp.take`` (``jax.vjp``) and to ``zeros.at[ids].add(dx)``,
added onto the head's gradient.

The kernel adds each table row's terms in float32, the head's first and then
the rows' in their order, and rounds once: for a float32 table that is the
transpose's own arithmetic on the CPU, bit for bit; a bfloat16 table's
gradient is the float32 transpose of the widened rows, rounded once."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import table_grad

D = 128


def ids_of(case, V, T):
    """The step's ids for each case; every case holds ids at the first and
    the last row of a block and of the table."""
    rng = np.random.default_rng(11)
    block = table_grad._block(V)
    if case == "one_id":
        ids = np.full(T, 7)
    elif case == "two_ids_alternating":
        ids = np.where(np.arange(T) % 2 == 0, 3, V - 1)
    elif case == "pad_id_0":
        ids = np.where(np.arange(T) % 3 == 0, 0, rng.integers(1, V, T))
    elif case == "out_of_range":
        # take counts -1 from the end; V, -V - 1 and beyond read a fill and add nothing
        ids = rng.choice(np.array([-1, V, -V - 1, 2 * V, 0, 5, V - 1]), T)
    elif case == "block_edges":
        edges = np.concatenate([np.arange(0, V, block), np.arange(block - 1, V, block), [V - 1]])
        ids = rng.choice(edges, T)
    else:  # unsorted: every row once at most, in a random order
        ids = rng.permutation(V)[:T]
    return jnp.asarray(ids, jnp.int32)


def take_transpose(ids, dx, V, dtype):
    """``jax.vjp`` of ``jnp.take`` over a table of ``dtype``, the rows'
    gradient widened to it."""
    table = jnp.zeros((V, dx.shape[-1]), dtype)
    return jax.vjp(lambda t: jnp.take(t, ids, axis=0), table)[1](dx.astype(dtype))[0]


def added(dhead, ids, dx):
    """``dhead`` plus ``dx``'s rows at their ids in float32, in their order,
    rounded once; by ``take``'s rules, a negative id counts from the end and
    every other id outside the table adds nothing."""
    V = dhead.shape[0]
    ids = jnp.where(ids < 0, ids + V, ids)
    ids = jnp.where((ids >= 0) & (ids < V), ids, V)
    return dhead.astype(jnp.float32).at[ids].add(
        dx.astype(jnp.float32), mode="drop").astype(dhead.dtype)


def tied_grad(table, ids, dx, dhead):
    """The forward of ``lookup`` and the table's gradient from the
    rows' gradient ``dx`` and the head's ``dhead``."""
    out, vjp = jax.vjp(table_grad.lookup, table, ids)
    return out, vjp((dx, dhead))[0]


def bits(a):
    return np.asarray(jax.lax.bitcast_convert_type(a, jnp.uint16 if a.dtype.itemsize == 2
                                                   else jnp.uint32))


CASES = ["one_id", "two_ids_alternating", "pad_id_0", "out_of_range", "block_edges", "unsorted"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("V,T", [(25008, 4096), (1001, 296)], ids=["V25008", "V1001"])
@pytest.mark.parametrize("case", CASES)
def test_the_gradient_is_take_s_transpose(case, V, T, dtype):
    """At the cell's table (25 008 rows: no multiple of the 512-row block)
    and at one of 1 001 rows (no multiple of 8 or 16) over 296 ids (a
    bfloat16 tile and a half): the kernel's gradient is the float32
    transpose of ``take``, rounded once to the table's dtype, and equals
    ``zeros.at[ids].add(dx)`` where every id is a row; the forward is
    ``take``'s, bit for bit (the fill included)."""
    ids = ids_of(case, V, T)
    dx = jax.random.normal(jax.random.PRNGKey(3), (T, D), jnp.float32).astype(dtype)
    table = jax.random.normal(jax.random.PRNGKey(4), (V, D), jnp.float32).astype(dtype)
    assert table_grad.takes_kernel(V, D, T)
    (rows, head), got = tied_grad(table, ids, dx, jnp.zeros_like(table))
    np.testing.assert_array_equal(bits(rows), bits(jnp.take(table, ids, axis=0)))
    np.testing.assert_array_equal(bits(head), bits(table))
    assert got.dtype == dtype and got.shape == (V, D)
    np.testing.assert_array_equal(got, take_transpose(ids, dx, V, jnp.float32).astype(dtype))
    if case != "out_of_range":
        np.testing.assert_array_equal(
            got, jnp.zeros((V, D), jnp.float32).at[ids].add(dx.astype(jnp.float32)).astype(dtype))
    if dtype == jnp.float32:
        np.testing.assert_array_equal(got, take_transpose(ids, dx, V, dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_rows_are_added_onto_the_head_s_gradient(dtype):
    """With a head's gradient beside the rows': each table row is the head's
    value widened, plus its rows' in their order, rounded once; a row no id
    names is the head's, bit for bit."""
    V, T = 1001, 296
    ids = ids_of("pad_id_0", V, T)
    dx = jax.random.normal(jax.random.PRNGKey(3), (T, D), jnp.float32).astype(dtype)
    dhead = jax.random.normal(jax.random.PRNGKey(6), (V, D), jnp.float32).astype(dtype)
    _, got = tied_grad(jnp.zeros((V, D), dtype), ids, dx, dhead)
    np.testing.assert_array_equal(got, added(dhead, ids, dx))
    unnamed = np.setdiff1d(np.arange(V), np.asarray(ids))
    assert unnamed.size and np.array_equal(bits(got[unnamed]), bits(dhead[unnamed]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_an_unread_table_takes_the_rows_onto_zeros(dtype):
    """An untied model reads only the rows: the head's gradient is then
    autodiff's zeros, and the table's is the kernel's sum of the rows alone,
    ``take``'s transpose widened to float32 and rounded once."""
    V, T = 1001, 296
    ids = ids_of("pad_id_0", V, T)
    dx = jax.random.normal(jax.random.PRNGKey(3), (T, D), jnp.float32).astype(dtype)
    table = jax.random.normal(jax.random.PRNGKey(4), (V, D), jnp.float32).astype(dtype)
    rows, vjp = jax.vjp(lambda t: table_grad.lookup(t, ids)[0], table)
    np.testing.assert_array_equal(bits(rows), bits(jnp.take(table, ids, axis=0)))
    np.testing.assert_array_equal(
        vjp(dx)[0], take_transpose(ids, dx, V, jnp.float32).astype(dtype))


@pytest.mark.parametrize("fold", [True, False], ids=["one_call", "take_s_transpose"])
def test_a_batch_of_tables_is_the_batch_of_their_gradients(fold, monkeypatch):
    """Under a clients' vmap the tables' gradients are each client's own:
    one call over the tables stacked one above the other where the stack
    fits the kernel, ``take``'s transpose per client where it does not;
    ids beyond a client's table never reach the next one's rows."""
    if not fold:
        monkeypatch.setattr(table_grad, "_MAX_ROWS", 300)
    jax.clear_caches()
    table_grad._batchable.cache_clear()
    try:
        V, T = 300, 256
        ids = jnp.stack([ids_of("out_of_range", V, T), ids_of("pad_id_0", V, T),
                         ids_of("block_edges", V, T)])
        dx = jax.random.normal(jax.random.PRNGKey(5), (3, T, D), jnp.float32)
        dhead = jax.random.normal(jax.random.PRNGKey(6), (3, V, D), jnp.float32)
        _, got = jax.vmap(tied_grad)(jnp.zeros((3, V, D), jnp.float32), ids, dx, dhead)
        # in the kernel's order where one call takes the stack; take's
        # transpose sums a row's terms before adding them to the head's
        close = np.testing.assert_array_equal if fold else functools.partial(
            np.testing.assert_allclose, rtol=1e-6, atol=1e-5)
        for k in range(3):
            close(got[k], added(dhead[k], ids[k], dx[k]))
        # one client's ids against every client's rows' gradients
        shared = jax.vmap(lambda x: table_grad.table_grad(ids[0], x, dhead[0]))(dx)
        close(shared[1], added(dhead[0], ids[0], dx[1]))
    finally:
        jax.clear_caches()
        table_grad._batchable.cache_clear()


@pytest.mark.parametrize("V,d,T,takes", [
    (25008, 2560, 4096, True),    # the SambaY cell's step
    (25008, 2560, 16384, False),  # its evaluation's 256 x 64 tokens: dx outgrows VMEM
    (16384, 2048, 4096, True),
    (61, 64, 24, False),          # rows of under a lane tile
    (25008, 2560, 0, False),
])
def test_the_decision_is_of_shapes_alone(V, d, T, takes):
    assert table_grad.takes_kernel(V, d, T) is takes
