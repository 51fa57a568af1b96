"""ops/slot_sum.py: the sum over a token's slots as a Pallas kernel that
reads only the rows of live slots, run interpreted here, against
``models/decoder.sum_readers``; the decision between the two at the
routed-expert cells' shapes; and the two places the decoder calls it
(``weighted_rows`` forward, ``take_rows`` backward) and ``routed_experts``
through it under both client schedules, the rule forced open at small
widths.

Where a case asks for the last bit, every product ``row * weight`` is exact
(rows and weights rounded through bfloat16): XLA's CPU backend may contract
the interpreted kernel's multiply and add into one rounding, which the chip
does not, so the sums agree to the bit only where the products need no
rounding (``chip_smoke``-style checks on the chip hold the full-precision
case to the bit)."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import create_model
from fedml_tpu.models.decoder import (routed_experts, row_bound, sum_readers, take_rows,
                                      weighted_rows)
from fedml_tpu.ops import slot_sum as op
from test_decoder import expert_weights, towards_held

ROOT = pathlib.Path(__file__).resolve().parents[1]

# tokens, top_k, width, table rows, share of live slots
CASES = {
    "tokens_with_no_live_slot": (256, 8, 128, 512, 0.06),
    "every_slot_live": (64, 4, 256, 256, 1.0),
    "no_slot_live": (64, 4, 128, 128, 0.0),
    "tokens_in_no_whole_tile": (600, 4, 128, 768, 0.25),
    "a_few_tokens_and_rows": (37, 3, 64, 40, 0.2),
}


def _operands(N, top_k, d, R, share, dtype, seed=0):
    """A table whose rows after the live ones hold NaN (no slot reads them),
    readers that send each live slot to its own row and every other slot to
    ``R``, and weights; rows and weights rounded through bfloat16."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    live = np.asarray(jax.random.uniform(ks[0], (N * top_k,)) < share)
    pairs = int(live.sum())
    assert pairs <= R
    readers = np.full(N * top_k, R, np.int32)
    readers[live] = np.asarray(jax.random.permutation(ks[1], pairs))
    table = jax.random.normal(ks[2], (R, d)).astype(jnp.bfloat16).astype(dtype)
    table = table.at[pairs:].set(jnp.nan)
    weights = jax.random.uniform(ks[3], (N, top_k)).astype(jnp.bfloat16).astype(jnp.float32)
    return table, jnp.asarray(readers.reshape(N, top_k)), weights


@pytest.mark.parametrize("weighted", [True, False], ids=["weighted", "unweighted"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_equals_sum_readers_to_the_bit(case, dtype, weighted):
    N, top_k, d, R, share = CASES[case]
    table, readers, weights = _operands(N, top_k, d, R, share, dtype)
    w = weights if weighted else None
    got = op.slot_sum(table, readers, w)
    want = sum_readers(table, readers, w)
    assert got.dtype == jnp.float32 and got.shape == (N, d)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if share == 0.0:
        assert not got.any()


def test_full_precision_products_agree_within_the_cpu_s_contraction():
    """Float32 rows times float32 weights, rounded once each on the chip;
    here the interpreted kernel's multiply-add may round once for both."""
    table, readers, _ = _operands(256, 8, 128, 512, 0.12, jnp.float32, seed=1)
    table = jax.random.normal(jax.random.PRNGKey(5), table.shape)
    weights = jax.random.uniform(jax.random.PRNGKey(6), readers.shape)
    got = op.slot_sum(table, readers, weights)
    want = sum_readers(table, readers, weights)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-7, atol=2e-7)


def test_a_table_of_another_dtype_is_refused():
    table, readers, _ = _operands(8, 2, 128, 16, 0.5, jnp.float32)
    with pytest.raises(ValueError, match="bfloat16 or float32"):
        op.slot_sum(table.astype(jnp.float16), readers)


# cell -> whether its training step's sums take the kernel: not where the
# table's rows are half the slots of 4 slots a token (8 192 rows for 4 096
# tokens x top-4)
EXPERT_CELLS = {"mellum2-12b-a2.5b.silo2": True, "kanana-2-30b-a3b.silo2b1": True,
                "lfm2-8b-a1b.silo2t4k": False, "nemotron-twotower-30b-a3b.silo2t4k-ssm": True,
                "laguna-xs.2.silo2t4k-swa": True}


@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_the_routed_expert_cells_training_steps_take_the_kernel(cell):
    """Host only: each cell's model from its configuration file, the tokens
    of a training step from its traffic file: both sums of every expert
    layer take one path, the kernel where a token has 6 slots or more and
    the table's rows are at most three eighths of the slots; the rehearsal's
    widths and the evaluation's batch
    of 16 384 tokens (a table of 12 288 rows or more) keep
    ``sum_readers``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    cfg = json.loads((ROOT / "benchmarks" / "configs" / f"{entry['config']}.json").read_text())
    traffic = json.loads((ROOT / "benchmarks" / "traffic" / f"{entry['traffic']}.json").read_text())
    for spec, takes in ((cfg["model"], EXPERT_CELLS[cell]), (cfg["rehearse"]["model"], False)):
        model = create_model(spec["name"], spec["dataset"], tuple(spec["input_shape"]),
                             int(spec["num_classes"]), **spec.get("kwargs", {}))
        sites = model.module.slot_sites(traffic["batch_size"] * spec["input_shape"][0])
        assert sites and len(sites) == 2 * model.flush_attrs(1)["expert_layers"]
        assert [op.takes_kernel(*s) for s in sites] == [takes] * len(sites)
        if spec is cfg["model"]:
            assert not any(op.takes_kernel(*s) for s in model.module.slot_sites(16384))


@pytest.mark.parametrize("N,top_k,d,R,takes", [
    (4096, 8, 2304, 8192, True), (2048, 6, 2048, 1536, True),
    (4096, 6, 2688, 3072, True), (4096, 8, 2048, 4096, True),
    (4096, 8, 1024, 12288, True), (4096, 6, 1024, 9216, True),  # three eighths
    (4096, 8, 1024, 12416, False), (4096, 6, 1024, 9344, False),  # a chunk more
    (4096, 8, 2304, 12288, False),  # three eighths, but 121.5 MiB in float32
    (4096, 8, 1024, 16384, False),  # the table's rows are half the slots
    (4096, 4, 2048, 8192, False),  # half, of 4 slots a token
    (4096, 4, 2048, 4096, False),  # a quarter: with 4 slots the kernel loses there too
    (64, 2, 32, 128, False),  # rows under a lane tile
    (4096, 8, 2304, 8000, False),  # a table in no whole number of DMA chunks
    (4096, 7, 2048, 4096, False),  # a tile's 3 584 readers fill no whole SMEM tile
    (16384, 8, 2304, 32768, False),  # silo2's evaluation: the table outgrows VMEM
])
def test_the_decision_reads_the_shapes_alone(N, top_k, d, R, takes):
    assert op.takes_kernel(N, top_k, d, R) is takes


def _both_paths(fn, *args, monkeypatch):
    """``fn(*args)`` by ``sum_readers`` and by the kernel, and the kernel's
    calls in each traced program."""
    out = []
    for takes in (False, True):
        jax.clear_caches()
        monkeypatch.setattr(op, "takes_kernel", lambda *shape: takes)
        calls = str(jax.make_jaxpr(fn)(*args)).count("name=slot_sum")
        assert bool(calls) is takes
        out.append(fn(*args))
    monkeypatch.undo()
    jax.clear_caches()
    return out


def _close(a, b, rel):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert float(np.max(np.abs(a - b))) <= rel * float(np.max(np.abs(b))) + 1e-30


def test_the_gradients_of_weighted_rows_and_take_rows_match_the_xla_path(monkeypatch):
    """The dispatch gather, a product over the sorted rows and the weighted
    sum, as ``_held_rows`` chains them: value and the gradients towards the
    tokens, the product's weights and the top-k weights. The kernel is the
    sum's forward and the gather's backward."""
    N, top_k, d, R = 96, 4, 128, 160
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    live = int(0.4 * N * top_k)
    slot = np.asarray(jax.random.permutation(ks[0], N * top_k))[:R]  # the flat place of each row
    readers = np.full(N * top_k, R, np.int32)
    readers[slot[:live]] = np.arange(live)
    readers = jnp.asarray(readers.reshape(N, top_k))
    slot = jnp.asarray(slot, jnp.int32)
    rows = (jnp.arange(R) < live)[:, None]
    x = jax.random.normal(ks[1], (N, d))
    w = jax.random.normal(ks[2], (d, d)) / 16
    top_w = jax.random.uniform(ks[3], (N, top_k))

    def loss(x, w, top_w):
        xs = jnp.where(rows, take_rows(x, slot // top_k, readers), 0.0)
        ys = jnp.where(rows, jnp.tanh(xs @ w), 0.0)
        return jnp.sum(jnp.sin(weighted_rows(ys, top_w, readers, slot)))

    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    (v0, g0), (v1, g1) = _both_paths(grad, x, w, top_w, monkeypatch=monkeypatch)
    assert abs(float(v0 - v1)) <= 1e-6 * abs(float(v0))
    for a, b in zip(g1, g0):
        _close(a, b, 1e-6)


@pytest.mark.parametrize("tokens,experts,towards", [(512, 16, False), (700, 32, True)],
                         ids=["under_the_bound", "over_it_twice_ragged_last_chunk"])
def test_the_token_side_reads_only_the_rows_of_held_pairs(tokens, experts, towards, monkeypatch):
    """Two experts held: the bound's rows after the held pairs hold
    pairs of experts on other chips, cleared, and no slot reads them in
    either sum (the weighted sum forward, the dispatch gather's backward), in
    the pass every step runs and in the overflow's chunks: a chunk's readers
    name exactly its first ``min(bound, pairs - start)`` rows."""
    from fedml_tpu.models import decoder

    seen, plain = [], decoder.sum_slots

    def sum_slots(table, readers, weights=None):
        jax.debug.callback(lambda r: seen.append(np.asarray(r)), readers)
        return plain(table, readers, weights)

    monkeypatch.setattr(decoder, "sum_slots", sum_slots)
    jax.clear_caches()
    lo, hi, top_k = 5, 7, 4
    x, router, gate, up, down = expert_weights(jax.random.PRNGKey(4), experts=experts, tokens=tokens)
    if towards:
        x, router = towards_held(x, router, lo, hi)

    def loss(x):
        y, counters = routed_experts(x, router, gate[lo:hi], up[lo:hi], down[lo:hi],
                                     top_k=top_k, held_from=lo)
        return jnp.sum(jnp.sin(y.astype(jnp.float32))), counters

    (_, counters), _ = jax.jit(jax.value_and_grad(loss, has_aux=True))(x)
    jax.effects_barrier()
    monkeypatch.undo()
    jax.clear_caches()
    bound, pairs = row_bound(tokens * top_k, hi - lo, experts), int(counters[0])
    chunks = [min(bound, pairs - start) for start in range(0, pairs, bound)]
    assert (len(chunks) > 1) is towards and len(seen) >= 2 * len(chunks)
    for readers in seen:
        live = np.sort(readers[readers < bound])
        assert len(live) in chunks and np.array_equal(live, np.arange(len(live)))
    assert sorted({int(np.sum(r < bound)) for r in seen}) == sorted(set(chunks))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("schedule", ["vmap", "scan"])
def test_routed_experts_through_the_kernel_equals_the_xla_path(schedule, dtype, monkeypatch):
    """Two clients on shared experts, 2 of 16 held and the router sent
    towards them so that the held pairs overflow the row bound: the kernel
    runs in the pass every step runs and in the overflow's loop, forward and
    backward. Value and the gradients towards the tokens, the router and the
    held experts' weights, per client, under the clients' vmap (one kernel
    call a member, ``_any_batched``) and their scan."""
    lo, hi, top_k = 5, 7, 4
    x, router, gate, up, down = expert_weights(jax.random.PRNGKey(3), experts=16, tokens=512)
    x, router = towards_held(x, router, lo, hi)
    xs = jnp.stack([x, jnp.roll(x, 7, axis=0)]).astype(dtype)
    held = [a[lo:hi].astype(dtype) for a in (gate, up, down)]
    router = router.astype(dtype)

    def member(x, router, gate, up, down):
        y, counters = routed_experts(x, router, gate, up, down, top_k=top_k, held_from=lo)
        return jnp.sum(jnp.sin(y.astype(jnp.float32))), counters

    step = jax.value_and_grad(member, argnums=(0, 1, 2, 3, 4), has_aux=True)
    if schedule == "vmap":
        clients = jax.jit(jax.vmap(step, in_axes=(0, None, None, None, None)))
    else:
        clients = jax.jit(lambda xs, *w: jax.lax.map(lambda x: step(x, *w), xs))
    plain, kernel = _both_paths(clients, xs, router, *held, monkeypatch=monkeypatch)
    (v0, c0), g0 = plain
    (v1, c1), g1 = kernel
    # the held pairs overflowed the bound in both clients: the loop ran
    assert bool((c0[:, 6] == 1).all()) and np.array_equal(np.asarray(c0), np.asarray(c1))
    rel = 1e-6 if dtype == jnp.float32 else 2.0 ** -8
    _close(v1, v0, rel)
    for a, b in zip(g1, g0):
        _close(a, b, rel)
