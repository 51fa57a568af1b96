"""ops/grouped_matmul.py: the routed experts' grouped products as Pallas
kernels, run interpreted here, against XLA's ``ragged_dot`` /
``ragged_dot_general``; the decision between the two at the routed-expert
cells' training shapes; and ``models/decoder.grouped_dot`` through the
kernels under both client schedules."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import create_model
from fedml_tpu.models.decoder import _TO_WEIGHTS, grouped_dot
from fedml_tpu.ops.grouped_matmul import gmm, takes_kernel, tgmm
from test_decoder import grouped_products

ROOT = pathlib.Path(__file__).resolve().parents[1]

# rows, K, N, group sizes; the weights' block is a whole expert
CASES = {
    "an_empty_group": (256, 128, 128, [100, 0, 90, 66]),
    "a_group_straddling_a_row_tile": (384, 128, 128, [60, 200, 124]),
    "rows_after_the_last_group_hold_garbage": (384, 128, 128, [50, 70, 0, 80]),
    "widths_with_a_64_wide_remainder": (256, 320, 192, [0, 130, 70, 0]),
    "groups_inside_one_row_tile": (256, 192, 320, [40, 20, 0, 56]),
}
TOLERANCE = {jnp.float32: 1e-5, jnp.bfloat16: 2.0 ** -7}


def _rel(got, want):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) / float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_three_kernels_match_ragged_dot_on_the_live_rows(case, dtype):
    """``gmm_fwd``, ``gmm_dx`` (the weights read transposed where they lie)
    and ``gmm_dw`` against ``ragged_dot`` on the rows of the groups. Rows
    after the last group hold NaN: neither result reads them, ``tgmm``'s
    blocks are whole numbers and an empty group's block is zeros."""
    M, K, N, sizes = CASES[case]
    G, live = len(sizes), sum(sizes)
    sizes = jnp.asarray(sizes, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (M, K), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[1], (G, K, N), jnp.float32).astype(dtype)
    c = jax.random.normal(ks[2], (M, N), jnp.float32).astype(dtype)
    want_y = jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32)[:live]
    want_dx = jax.lax.ragged_dot(
        c, jnp.swapaxes(w, 1, 2), sizes, preferred_element_type=jnp.float32)[:live]
    want_dw = jax.lax.ragged_dot_general(x, c, sizes, _TO_WEIGHTS, preferred_element_type=jnp.float32)
    x, c = x.at[live:].set(jnp.nan), c.at[live:].set(jnp.nan)
    y = gmm(x, w, sizes)
    dx = gmm(c, w, sizes, transposed=True)
    dw = tgmm(x, c, sizes)
    assert y.dtype == dx.dtype == dw.dtype == dtype
    assert y.shape == (M, N) and dx.shape == (M, K) and dw.shape == (G, K, N)
    tol = TOLERANCE[dtype]
    assert _rel(y[:live], want_y) <= tol
    assert _rel(dx[:live], want_dx) <= tol
    assert bool(jnp.isfinite(dw).all()) and _rel(dw, want_dw) <= tol
    for g in np.flatnonzero(np.asarray(sizes) == 0):
        assert not dw[g].any()


# cell -> grouped products a step runs outside the overflow loops
EXPERT_CELLS = {
    "mellum2-12b-a2.5b.silo2": 36,
    "kanana-2-30b-a3b.silo2b1": 36,
    "lfm2-8b-a1b.silo2t4k": 36,
    "nemotron-twotower-30b-a3b.silo2t4k-ssm": 18,
}


@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
def test_the_routed_expert_cells_training_shapes_take_the_kernels(cell):
    """Host only: each cell's model from its configuration file, the rows of
    a training step from its traffic file, and every grouped product of the
    step sent to the kernels; the rehearsal's widths keep ``ragged_dot``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    cfg = json.loads((ROOT / "benchmarks" / "configs" / f"{entry['config']}.json").read_text())
    traffic = json.loads((ROOT / "benchmarks" / "traffic" / f"{entry['traffic']}.json").read_text())
    for spec, takes in ((cfg["model"], True), (cfg["rehearse"]["model"], False)):
        model = create_model(spec["name"], spec["dataset"], tuple(spec["input_shape"]),
                             int(spec["num_classes"]), **spec.get("kwargs", {}))
        shapes = model.module.grouped_sites(traffic["batch_size"] * spec["input_shape"][0])
        assert [takes_kernel(*s) for s in shapes] == [takes] * len(shapes)
        if takes:
            assert 3 * len(shapes) == EXPERT_CELLS[cell]


@pytest.mark.parametrize("M,K,N,G,takes", [
    (8192, 2304, 896, 8, True), (1536, 2048, 768, 8, True),
    (8192 + 64, 2304, 896, 8, False),  # rows in no whole number of tiles
    (3072, 2688, 1856, 8, True),  # the widest expert of the four cells
    (512, 64, 32, 4, False), (512, 2048, 64, 4, False),  # a width under a lane tile
    (8192, 4096, 2048, 8, False),  # a whole expert's blocks outgrow the fast memory
])
def test_the_decision_reads_the_shapes_alone(M, K, N, G, takes):
    assert takes_kernel(M, K, N, G) is takes


@pytest.mark.parametrize("schedule", ["vmap", "scan"])
def test_grouped_dot_takes_the_kernels_with_its_value_and_both_gradients(schedule):
    """Two clients on shared weights, under both client schedules, at widths
    that take the kernels (interpreted here): value and the gradients
    towards the rows and the weights against each row times its own group's
    weights, in float32. The clients' vmap hands the rule unbatched weights,
    which ``_any_batched`` splits into one call a member."""
    M, K, N, G = 256, 128, 128, 4
    assert takes_kernel(M, K, N, G)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    rows = jax.random.normal(ks[0], (2, M, K))
    weights = jax.random.normal(ks[1], (G, K, N)) / 8
    sizes = jnp.asarray([[100, 0, 90, 66], [30, 130, 10, 40]], jnp.int32)

    def loss(rows, weights, sizes, dense):
        live = (jnp.arange(M) < jnp.sum(sizes))[:, None]
        if dense:
            group = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(M), side="right")
            y = jnp.einsum("mk,mkn->mn", rows, weights[jnp.minimum(group, G - 1)])
        else:
            y = grouped_dot(rows, weights, sizes)
        return jnp.sum(jnp.where(live, jnp.sin(y), 0.0))

    def member(dense):
        return jax.value_and_grad(lambda r, w, s: loss(r, w, s, dense), argnums=(0, 1))

    def clients(dense):
        if schedule == "vmap":
            return lambda r, w, s: jax.vmap(member(dense), in_axes=(0, None, 0))(r, w, s)
        return lambda r, w, s: jax.lax.scan(
            lambda _, m: (None, member(dense)(m[0], w, m[1])), None, (r, s))[1]

    # forward and both gradients: three kernels a member under vmap, once in
    # the scan's body
    found = grouped_products(jax.make_jaxpr(clients(False))(rows, weights, sizes).jaxpr)
    assert found["kernels"] == found["outside" if schedule == "vmap" else "in_loops"] == (
        6 if schedule == "vmap" else 3)
    (value, (g_rows, g_weights)) = clients(False)(rows, weights, sizes)
    (want, (w_rows, w_weights)) = clients(True)(rows, weights, sizes)
    np.testing.assert_allclose(value, want, rtol=1e-5)
    for c, live in enumerate(np.asarray(sizes).sum(axis=1)):
        # the rows after the last group hold nothing to rely on, their gradient too
        np.testing.assert_allclose(g_rows[c, :live], w_rows[c, :live],
                                   atol=1e-5 * float(jnp.max(jnp.abs(w_rows))))
    np.testing.assert_allclose(g_weights, w_weights, atol=1e-5 * float(jnp.max(jnp.abs(w_weights))))
