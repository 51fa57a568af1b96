"""The spec-driven decoder (models/decoder.py) against its plain reference
(benchmarks/configs/mellum2-12b-a2.5b_ref.py), at small sizes on the CPU in
float32 with seeded random weights: loss and every leaf's gradient, the
grouped form of the expert layer against the dense masked form (all rows,
under the row bound, and over it once and twice, under both client
schedules), the grouped products a layer's gradient holds, the shares of an
expert-parallel deployment against the uncut layer, one federated round under
both client schedules with the expert counters on the ``flush`` span, and the
reader of ``moe.bounded_call_pct`` on hand-made runs."""

import copy
import dataclasses
import importlib.util
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.compile import model_fingerprint
from fedml_tpu.models import create_model
from fedml_tpu.models.decoder import COUNTERS, _held_rows, grouped_dot, routed_experts, row_bound
from fedml_tpu.parallel.ring_attention import full_attention

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from benchmarks.lib import fedavg_ref  # noqa: E402

YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 16, "beta_fast": 32, "beta_slow": 1,
        "attention_factor": 1.2772588722239782}
PLAIN = {"rope_type": "default", "rope_theta": 500000}
BASE = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=4, head_dim=8,
    layer_types=["full_attention"], sliding_window=64,
    rope_parameters={"full_attention": PLAIN, "sliding_attention": PLAIN},
    num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16,
    norm_topk_prob=True, rms_norm_eps=1e-6, tie_word_embeddings=False,
)
CASES = {
    "window_shorter_than_sequence": dict(layer_types=["sliding_attention"], sliding_window=5),
    "four_query_heads_on_two_kv_heads": dict(num_key_value_heads=2),
    "yarn_layer_and_plain_rotary_layer": dict(
        layer_types=["sliding_attention", "full_attention"], sliding_window=6,
        rope_parameters={"full_attention": YARN, "sliding_attention": PLAIN}),
    "top2_of_8_with_4_held": dict(num_experts=8, experts_held=[2, 6]),
    "all_experts_held": dict(num_experts=8, num_experts_per_tok=3),
}
VOCAB, LENGTH = 61, 24


def reference():
    path = ROOT / "benchmarks" / "configs" / "mellum2-12b-a2.5b_ref.py"
    spec = importlib.util.spec_from_file_location("mellum_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(**over):
    return {"model": {"name": "decoder", "dataset": "random_tokens", "input_shape": [LENGTH],
                      "num_classes": VOCAB, "kwargs": dict(copy.deepcopy(BASE), **over)}}


def nest(flat):
    tree = {}
    for name, leaf in flat.items():
        node = tree
        *parents, last = name.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def flatten(tree):
    return {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("case", sorted(CASES))
def test_loss_and_every_gradient_match_the_reference(case):
    """Both sides are exact float32 on the CPU, so they differ by the order
    of their sums only: 2e-5 of a leaf's largest gradient (measured: under
    5e-7 in every case), 1e-6 of the loss (measured: equal). A top-k choice
    flipped, or a slot's weight off, shows as 1e-2 and more."""
    ref = reference()
    cfg = config(**CASES[case])
    m = cfg["model"]
    model = create_model(m["name"], m["dataset"], (LENGTH,), VOCAB, **m["kwargs"])
    flat = ref.init_params(5, cfg)
    have = jax.eval_shape(model.init, jax.random.PRNGKey(0))["params"]
    assert {k: v.shape for k, v in flatten(have).items()} == ref.param_shapes(cfg)
    doc = jax.random.randint(jax.random.PRNGKey(9), (3, LENGTH + 1), 1, VOCAB)
    x, y = doc[:, :-1], doc[:, 1:]
    mask = jnp.ones((3,), jnp.float32)

    def program_loss(flat):
        logits, _ = model.apply({"params": nest(flat)}, x, train=True)
        return fedavg_ref.task_loss("nwp", logits, y, mask)[0]

    def reference_loss(flat):
        return fedavg_ref.task_loss(
            "nwp", ref.logits_fn(flat, x, fedavg_ref.REFERENCE, cfg), y, mask)[0]

    loss_p, grad_p = jax.value_and_grad(program_loss)(flat)
    loss_r, grad_r = jax.value_and_grad(reference_loss)(flat)
    assert abs(float(loss_p) - float(loss_r)) <= 1e-6 * abs(float(loss_r))
    for name in grad_r:
        scale = float(jnp.max(jnp.abs(grad_r[name])))
        assert scale > 0, name
        gap = float(jnp.max(jnp.abs(grad_p[name] - grad_r[name])))
        assert gap <= 2e-5 * scale, (name, gap, scale)


def expert_weights(key, d=16, f=24, experts=8, tokens=40):
    ks = jax.random.split(key, 5)
    return (jax.random.normal(ks[0], (tokens, d)), jax.random.normal(ks[1], (d, experts)),
            0.3 * jax.random.normal(ks[2], (experts, d, f)),
            0.3 * jax.random.normal(ks[3], (experts, d, f)),
            0.3 * jax.random.normal(ks[4], (experts, f, d)))


def dense_masked(x, router, gate, up, down, top_k, lo, hi):
    """Every held expert on every token, weighted by the renormalised top-k
    probability of the slots that chose it (0 where none did)."""
    probs = jax.nn.softmax(x @ router, axis=-1)
    values, experts = jax.lax.top_k(probs, top_k)
    values = values / jnp.sum(values, axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(lo, hi):
        weight = jnp.sum(jnp.where(experts == e, values, 0.0), axis=-1, keepdims=True)
        out = out + weight * ((jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e])
    return out


def towards_held(x, router, lo, hi, by=8.0):
    """The same tokens and router with every token's first choices on the
    held experts: feature 0 is 3 on every token and weighs ``by`` for them."""
    return x.at[:, 0].set(3.0), router.at[0, lo:hi].add(by)


# tokens, experts, top_k, held, router biased towards the held experts
GROUPED = {
    "all_8_held": (40, 8, 3, (0, 8), False),
    "first_4_of_8": (40, 8, 3, (0, 4), False),
    "middle_2_of_8": (40, 8, 3, (3, 5), False),
    "under_the_bound_2_of_16": (512, 16, 4, (0, 2), False),
    "over_the_bound_2_of_16": (512, 16, 4, (5, 7), True),
    "over_it_twice_ragged_last_chunk": (700, 32, 4, (5, 7), True),
}


@pytest.mark.parametrize("case", sorted(GROUPED))
def test_grouped_form_matches_dense_masked_form(case):
    """Value and gradients towards the tokens, the router and the held
    experts' weights; float32 both ways, 1e-5 of the largest entry for the
    different order of the sums. Where the chip holds a small share the
    sorted rows are bounded (``row_bound``) and the comparison is of the
    bounded pass; with the router biased the held pairs exceed the bound and
    the overflow's passes are compared too: nothing is dropped."""
    tokens, experts, top_k, (lo, hi), biased = GROUPED[case]
    x, router, gate, up, down = expert_weights(
        jax.random.PRNGKey(3), experts=experts, tokens=tokens)
    if biased:
        x, router = towards_held(x, router, lo, hi)

    def grouped(x, router, gate, up, down):
        y, counters = routed_experts(x, router, gate[lo:hi], up[lo:hi], down[lo:hi],
                                     top_k=top_k, held_from=lo)
        return jnp.sum(jnp.sin(y)), counters

    def dense(x, router, gate, up, down):
        return jnp.sum(jnp.sin(dense_masked(x, router, gate, up, down, top_k, lo, hi)))

    (vg, counters), gg = jax.value_and_grad(grouped, argnums=(0, 1, 2, 3, 4), has_aux=True)(
        x, router, gate, up, down)
    vd, gd = jax.value_and_grad(dense, argnums=(0, 1, 2, 3, 4))(x, router, gate, up, down)
    assert abs(float(vg - vd)) <= 1e-5 * abs(float(vd))
    for a, b in zip(gg, gd):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7
    c = dict(zip(COUNTERS, np.asarray(counters)))
    rows = tokens * top_k
    bound = row_bound(rows, hi - lo, experts)
    assert c["moe_dropped"] == 0 and c["moe_calls"] == 1
    assert c["moe_pairs"] == rows if (lo, hi) == (0, experts) else c["moe_pairs"] < rows
    assert c["moe_load_mean"] == c["moe_pairs"] / (hi - lo) <= c["moe_load_max"]
    # the rows the grouped products ran over: the bound, once for every
    # `bound` held pairs or part of them
    assert c["moe_rows"] == bound * max(1, -(-int(c["moe_pairs"]) // bound))
    assert c["moe_overflow"] == (c["moe_pairs"] > bound) == biased
    if tokens == 40:
        assert bound == rows == c["moe_rows"] == 40 * 3  # every row, as before the bound
    elif biased:
        assert bound == 512 and c["moe_rows"] > bound
    else:
        assert bound == 512 == c["moe_rows"] < rows


def test_the_row_bound_is_twice_the_even_share_in_whole_tiles_and_never_over_the_rows():
    assert row_bound(32768, 8, 64) == 8192  # mellum2-12b-a2.5b.silo2's training step
    assert row_bound(131072, 8, 64) == 32768  # and its evaluation batch
    assert row_bound(2048, 2, 16) == 512
    assert row_bound(2800, 2, 32) == 512  # 350 rows, rounded up to a tile
    assert row_bound(120, 4, 8) == 120  # a tile is more than all rows
    for rows, experts in [(120, 8), (32768, 64), (96, 4)]:
        assert row_bound(rows, experts, experts) == rows  # every expert held: no bound


@pytest.mark.parametrize("held,loops", [((0, 16), 0), ((4, 6), 2)])
def test_only_a_held_share_puts_the_overflow_loop_in_the_program(held, loops):
    """Every expert held: the bound is all rows and the program has no loop
    or branch in it. A share of 2 in 16: one loop forward and one backward,
    of as many trips as the step's pairs ask for."""
    lo, hi = held
    x, router, gate, up, down = expert_weights(jax.random.PRNGKey(3), experts=16, tokens=512)

    def loss(x, router, gate, up, down):
        return jnp.sum(routed_experts(x, router, gate, up, down, top_k=4, held_from=lo)[0])

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 2)))(
        x, router, gate[lo:hi], up[lo:hi], down[lo:hi]))
    assert text.count("while[") == loops and "cond[" not in text


def grouped_products(jaxpr, in_loop=False, found=None):
    """Grouped products of a jaxpr and of every jaxpr inside it, those beneath
    a ``while`` or ``scan`` apart, and the loops themselves: ``ragged_dot*``
    equations and ``ops/grouped_matmul``'s ``pallas_call``s (``gmm_*``),
    which ``kernels`` counts again. A kernel's body is not the program's:
    its own loops are not counted."""
    found = ({"outside": 0, "in_loops": 0, "loops": 0, "kernels": 0}
             if found is None else found)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        loop = name in ("while", "scan")
        found["loops"] += loop
        kernel = name == "pallas_call" and eqn.params["name"].startswith("gmm_")
        found["kernels"] += kernel
        if name.startswith("ragged_dot") or kernel:
            found["in_loops" if in_loop else "outside"] += 1
        if name == "pallas_call":
            continue
        for inner in jax.tree_util.tree_leaves(
                list(eqn.params.values()), is_leaf=lambda v: hasattr(v, "eqns") or hasattr(v, "jaxpr")):
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                grouped_products(inner, in_loop or loop, found)
    return found


@pytest.mark.parametrize("held,in_loops,loops", [((0, 16), 0, 0), ((4, 8), 12, 2)])
def test_a_layers_gradient_holds_nine_grouped_products_outside_the_overflow_loops(
        held, in_loops, loops):
    """Three forward and six backward: the pass every step runs keeps its
    rows, so its backward runs no product a second time (twelve before PR
    35). The overflow's loops hold what they held: three forward, and three
    run again with six backward."""
    lo, hi = held
    x, router, gate, up, down = expert_weights(jax.random.PRNGKey(3), experts=16, tokens=512)

    def loss(x, router, gate, up, down):
        return jnp.sum(routed_experts(x, router, gate, up, down, top_k=4, held_from=lo)[0])

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
        x, router, gate[lo:hi], up[lo:hi], down[lo:hi])
    assert grouped_products(jaxpr.jaxpr) == {
        "outside": 9, "in_loops": in_loops, "loops": loops, "kernels": 0}


def test_at_mellum2s_training_shapes_the_nine_products_are_the_kernels():
    """``_held_rows``' gradient traced abstractly at
    ``mellum2-12b-a2.5b.silo2``'s step (2 x 2 048 tokens of 2 304, top-8,
    8 of 64 experts of 896 held: a bound of 8 192 rows): the nine grouped
    products are ``gmm_fwd`` x 3, ``gmm_dx`` x 3 and ``gmm_dw`` x 3, and no
    ``ragged_dot`` is left. Shapes only: nothing is computed."""
    tokens, d, f, top_k, held = 4096, 2304, 896, 8, 8
    bound = row_bound(tokens * top_k, held, 64)
    assert bound == 8192
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    operands = (bf16(tokens, d), jax.ShapeDtypeStruct((tokens, top_k), jnp.float32),
                bf16(held, d, f), bf16(held, d, f), bf16(held, f, d))
    index = (i32(tokens * top_k), i32(tokens, top_k), i32(held))

    def loss(*operands_and_index):
        return jnp.sum(_held_rows(jnp.int32(0), *operands_and_index, bound=bound))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*operands, *index)
    assert grouped_products(jaxpr.jaxpr) == {
        "outside": 9, "in_loops": 0, "loops": 0, "kernels": 9}


@pytest.mark.parametrize("schedule", ["scan", "vmap"])
def test_members_that_take_one_two_and_three_passes_match_the_dense_form(schedule):
    """Three clients on shared expert weights, under both client schedules:
    the first stays under the bound (its gradient is the kept rows' alone),
    the second runs the overflow's loop once, the third twice. Value and all
    five gradients of each against the dense masked form, as
    ``test_grouped_form_matches_dense_masked_form`` holds them."""
    tokens, experts, top_k, lo, hi = 700, 32, 4, 5, 7
    x, router, gate, up, down = expert_weights(
        jax.random.PRNGKey(3), experts=experts, tokens=tokens)
    x_all, router_all = towards_held(x, router, lo, hi)
    # feature 0 draws a token to the held experts: on no token, on half, on all
    xs = jnp.stack([x.at[:, 0].set(0.0), x_all.at[tokens // 2:, 0].set(0.0), x_all])
    routers = jnp.stack([router_all] * 3)

    def grouped(x, router, gate, up, down):
        y, counters = routed_experts(x, router, gate[lo:hi], up[lo:hi], down[lo:hi],
                                     top_k=top_k, held_from=lo)
        return jnp.sum(jnp.sin(y)), counters

    def dense(x, router, gate, up, down):
        return jnp.sum(jnp.sin(dense_masked(x, router, gate, up, down, top_k, lo, hi)))

    member = jax.value_and_grad(grouped, argnums=(0, 1, 2, 3, 4), has_aux=True)
    if schedule == "vmap":
        (values, counters), grads = jax.vmap(member, in_axes=(0, 0, None, None, None))(
            xs, routers, gate, up, down)
    else:
        _, ((values, counters), grads) = jax.lax.scan(
            lambda _, m: (None, member(m[0], m[1], gate, up, down)), None, (xs, routers))
    bound = row_bound(tokens * top_k, hi - lo, experts)
    passes = np.asarray(counters)[:, COUNTERS.index("moe_rows")] / bound
    assert list(passes) == [1, 2, 3]
    assert not np.asarray(counters)[:, COUNTERS.index("moe_dropped")].any()
    for m in range(3):
        vd, gd = jax.value_and_grad(dense, argnums=(0, 1, 2, 3, 4))(
            xs[m], routers[m], gate, up, down)
        assert abs(float(values[m] - vd)) <= 1e-5 * abs(float(vd))
        for a, b in zip(grads, gd):
            assert float(jnp.max(jnp.abs(a[m] - b))) <= 1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7


def test_members_of_a_vmap_overflow_each_on_their_own():
    """The clients' vmap batches the overflow loop's trip count: one member
    over the bound and one under it give what each gives alone."""
    lo, hi, top_k = 5, 7, 4
    x, router, gate, up, down = expert_weights(jax.random.PRNGKey(6), experts=16, tokens=512)
    x_over, router_over = towards_held(x, router, lo, hi)

    def layer(x, router):
        return routed_experts(x, router, gate[lo:hi], up[lo:hi], down[lo:hi],
                              top_k=top_k, held_from=lo)

    def loss(xs, routers):
        y, counters = jax.vmap(layer)(xs, routers)
        return jnp.sum(jnp.sin(y)), (y, counters)

    xs, routers = jnp.stack([x, x_over]), jnp.stack([router, router_over])
    (_, (ys, counters)), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(xs, routers)
    overflow = np.asarray(counters)[:, COUNTERS.index("moe_overflow")]
    assert list(overflow) == [0, 1]
    def alone(x, router):
        return jnp.sum(jnp.sin(layer(x, router)[0]))

    for m in range(2):
        y, _ = layer(xs[m], routers[m])
        gx, gr = jax.grad(alone, argnums=(0, 1))(xs[m], routers[m])
        assert jnp.allclose(ys[m], y, rtol=0, atol=1e-6)
        assert jnp.allclose(grads[0][m], gx, rtol=0, atol=1e-6)
        assert jnp.allclose(grads[1][m], gr, rtol=0, atol=1e-6)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """model-configs guide, section 4: 16 experts over 8 chips, 2 each. The
    shares' expert outputs, nothing counted twice (the layer has no shared
    expert and the router's weights are every chip's alike), add up to what
    the layer gives with every expert held, and their held pairs to all
    tokens x top-k. 1e-5: the order of eight partial sums in float32."""
    x, router, gate, up, down = expert_weights(jax.random.PRNGKey(4), experts=16)
    whole, counted = routed_experts(x, router, gate, up, down, top_k=4)
    parts, pairs = jnp.zeros_like(whole), 0.0
    for chip in range(8):
        lo = 2 * chip
        y, c = routed_experts(x, router, gate[lo:lo + 2], up[lo:lo + 2], down[lo:lo + 2],
                              top_k=4, held_from=lo)
        parts, pairs = parts + y, pairs + float(c[0])
    assert pairs == float(counted[0]) == 40 * 4
    assert float(jnp.max(jnp.abs(parts - whole))) <= 1e-5 * float(jnp.max(jnp.abs(whole)))


def test_grouped_dot_batches_over_clients_with_unbatched_weights():
    """What the clients' vmap does on its first pass over the local steps'
    scan: rows and sizes per client, weights not yet."""
    rows = jax.random.normal(jax.random.PRNGKey(0), (3, 12, 5))
    weights = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 4))
    sizes = jnp.asarray([[4, 6], [0, 12], [5, 0]], jnp.int32)
    got = jax.vmap(grouped_dot, in_axes=(0, None, 0))(rows, weights, sizes)
    for c in range(3):
        want = jax.lax.ragged_dot(rows[c], weights, sizes[c])
        live = int(sizes[c].sum())
        assert jnp.allclose(got[c, :live], want[:live], atol=1e-6)


def naive_attention(q, k, v, window):
    B, T, H, D = q.shape
    k = jnp.repeat(k, H // k.shape[2], axis=2)
    v = jnp.repeat(v, H // v.shape[2], axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    keep = (j <= i) & ((i - j < window) if window else True)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(keep, s, -1e30), -1), v)


@pytest.mark.parametrize("kv_heads,window", [(4, 3), (2, None), (2, 5), (1, 16)])
def test_full_attention_window_and_grouped_queries(kv_heads, window):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 16, 4, 8))
    k = jax.random.normal(ks[1], (2, 16, kv_heads, 8))
    v = jax.random.normal(ks[2], (2, 16, kv_heads, 8))
    got = full_attention(q, k, v, causal=True, window=window)
    assert jnp.allclose(got, naive_attention(q, k, v, window), atol=2e-6)


def test_full_attention_traces_as_before_without_window_or_groups():
    """``gpt2-124m.silo4`` runs this path: the jaxpr of a call with equal
    head counts and no window is the one the function gave before it knew
    either (its body then, restated here)."""

    def before(q, k, v):
        D = q.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(D, jnp.float32))
        T = q.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)

    q = jnp.zeros((2, 8, 4, 8), jnp.bfloat16)
    now = jax.make_jaxpr(lambda q, k, v: full_attention(q, k, v, causal=True))(q, q, q)
    assert str(now) == str(jax.make_jaxpr(before)(q, q, q))


def test_create_model_builds_it_and_the_spec_arrives_whole():
    spec = dict(CASES["yarn_layer_and_plain_rotary_layer"], experts_held=[1, 3])
    model = create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **dict(BASE, **spec))
    assert tuple(model.module.layer_types) == ("sliding_attention", "full_attention")
    assert dict(model.module.rope_parameters["full_attention"]) == YARN
    assert model.counters == COUNTERS and model.flush_attrs(1)["layers"] == 2
    params = model.init(jax.random.PRNGKey(0))
    assert set(params) == {"params"}
    assert params["params"]["layers_1"]["experts_gate"].shape == (2, 32, 16)
    logits, _ = model.apply(params, jnp.ones((2, LENGTH), jnp.int32), train=False)
    assert logits.shape == (2, LENGTH, VOCAB)
    assert create_model("decoder", "x", (16,), 50).name == "decoder"  # the CLI's call


@pytest.mark.parametrize("change", [
    dict(layer_types=["full_attention", "sliding_attention"]),
    dict(rope_parameters={"full_attention": dict(YARN, factor=8), "sliding_attention": PLAIN}),
    dict(experts_held=[0, 2]),
    dict(sliding_window=7),
])
def test_model_fingerprint_tells_two_specs_apart(change):
    spec = dict(BASE, **CASES["yarn_layer_and_plain_rotary_layer"])
    one = create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **spec)
    same = create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **copy.deepcopy(spec))
    other = create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **dict(spec, **change))
    assert model_fingerprint(one) == model_fingerprint(same)
    assert model_fingerprint(one) != model_fingerprint(other)


def test_transformer_moe_message_points_to_the_decoder():
    with pytest.raises(ValueError, match="'decoder' model"):
        create_model("transformer", "shakespeare", (80,), 90, moe_experts=4)


# One step of 8 documents of 64 tokens, top-2 of 16 experts with 2 held:
# 1 024 (token, slot) rows under a bound of 512, about 128 of them held.
ROUND = dict(length=64, docs=8, clients=3, num_experts=16, experts_held=[2, 4])


def one_round(mode):
    from fedml_tpu.algorithms import FedAvgAPI
    from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
    from fedml_tpu.data.base import FederatedDataset
    from fedml_tpu.telemetry import get_tracer

    length, per_client, clients = ROUND["length"], ROUND["docs"], ROUND["clients"]
    docs = np.random.default_rng(0).integers(
        1, VOCAB, size=(clients, per_client, length + 1), dtype=np.int32)
    data = FederatedDataset(
        name="random_tokens", client_x=list(docs[:, :, :-1]), client_y=list(docs[:, :, 1:]),
        test_x=docs[0, :2, :-1], test_y=docs[0, :2, 1:], num_classes=VOCAB)
    model = create_model(
        "decoder", "random_tokens", (length,), VOCAB,
        **dict(BASE, num_experts=ROUND["num_experts"], experts_held=ROUND["experts_held"]))
    cfg = RunConfig(
        data=DataConfig(batch_size=per_client, pad_bucket=1),
        fed=FedConfig(client_num_in_total=clients, client_num_per_round=clients, comm_round=1,
                      epochs=1, client_parallelism=mode),
        train=TrainConfig(client_optimizer="sgd", lr=0.05), model="decoder", seed=1,
    )
    tracer = get_tracer()
    t0 = tracer.now_us()
    api = FedAvgAPI(cfg, data, model, task="nwp", log_fn=lambda row: None)
    api.train()
    flushes = [e.attrs for e in tracer.events() if e.name == "flush" and e.ts_us >= t0]
    return api, flushes


def test_a_federated_round_is_the_same_under_vmap_and_scan_and_counts_its_experts():
    vmapped, flushes = one_round("vmap")
    scanned, _ = one_round("scan")
    assert (vmapped._client_mode, scanned._client_mode) == ("vmap", "scan")
    for a, b in zip(jax.tree_util.tree_leaves(vmapped.global_vars),
                    jax.tree_util.tree_leaves(scanned.global_vars)):
        # the same float32 steps, batched or one client after another
        assert jnp.allclose(a, b, rtol=0, atol=1e-6)
    assert len(flushes) == 1
    attrs = flushes[0]
    calls, layers, top_k = ROUND["clients"], 1, 2  # one step a client, one layer
    rows = ROUND["docs"] * ROUND["length"] * top_k
    bound = row_bound(rows, 2, ROUND["num_experts"])
    assert bound == 512 < rows
    assert attrs["moe_dropped"] == 0
    assert (attrs["moe_calls"], attrs["moe_overflow"]) == (calls * layers, 0)
    assert attrs["moe_rows"] == calls * layers * bound  # not tokens x top-k: the bounded rows
    assert 0 < attrs["moe_pairs"] <= attrs["moe_rows"]
    assert attrs["moe_load_mean"] * 2 == attrs["moe_pairs"]
    assert attrs["moe_load_max"] >= attrs["moe_load_mean"]
    assert (attrs["hidden"], attrs["expert_width"], attrs["layers"]) == (32, 16, 1)


def bounded_call_pct():
    path = ROOT / "benchmarks" / "metrics" / "moe.bounded_call_pct.py"
    spec = importlib.util.spec_from_file_location("moe_bounded_call_pct", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("flushes,want", [
    ([dict(moe_calls=64.0, moe_overflow=0.0), dict(moe_calls=320.0, moe_overflow=0.0)], 100.0),
    ([dict(moe_calls=64.0, moe_overflow=16.0), dict(moe_calls=64.0, moe_overflow=48.0)], 50.0),
    ([dict(moe_calls=8.0, moe_overflow=8.0)], 0.0),  # every call over its bound: a reading
    ([dict(rows=5)], None),  # the parent's spans, and a model without routed experts
    ([], None),
])
def test_bounded_call_share_reads_the_flush_spans_and_is_absent_without_them(flushes, want):
    """The reader as ``benchmarks/run.py`` loads it, by path; other spans and
    flushes without the counters are not its to read, and an absent share is
    ``None``, never 0."""
    spans = [("round", 0, 5, {"clients": 2})] + [("flush", 10 * i, 10 * i + 5, dict(a, rows=5))
                                                for i, a in enumerate(flushes)]
    got = bounded_call_pct()({"program_spans": spans})
    assert got == want and (want is None or isinstance(got, float))


def test_a_model_without_counters_keeps_its_metrics_and_flush_span():
    from fedml_tpu.config import TrainConfig
    from fedml_tpu.train.client import make_local_train

    model = create_model("lr", "synthetic", (6,), 3)
    assert model.counters == ()
    train = make_local_train(model, TrainConfig(lr=0.1), epochs=1)
    variables = model.init(jax.random.PRNGKey(0))
    x = jnp.ones((2, 4, 6))
    _, metrics = train(variables, x, jnp.zeros((2, 4), jnp.int32), jnp.ones((2, 4)),
                       jax.random.PRNGKey(1))
    assert sorted(metrics) == ["correct", "count", "loss_sum", "steps"]
    out, same, counted = model.apply(variables, x[0], train=True, counters=True)
    assert counted.shape == (0,) and same is variables


def test_auto_takes_scan_for_a_gigabyte_of_parameters_and_vmap_below():
    from fedml_tpu.algorithms.fedavg import resolve_client_parallelism

    big = dataclasses.replace(create_model("lr", "synthetic", (6,), 3))
    big.init = lambda rng: {"params": {"w": jax.ShapeDtypeStruct((2**28,), jnp.float32)}}
    below = dataclasses.replace(big)
    below.init = lambda rng: {"params": {"w": jax.ShapeDtypeStruct((2**28 - 1,), jnp.float32)}}
    assert resolve_client_parallelism("auto", big) == "scan"
    assert resolve_client_parallelism("auto", below) == "vmap"
    gpt2 = create_model("transformer", "random_tokens", (1024,), 50257,
                        num_layers=12, num_heads=12, embed_dim=768)
    assert resolve_client_parallelism("auto", gpt2) == "vmap"
