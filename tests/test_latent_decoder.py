"""The decoder's second block shape (latent attention, a leading dense layer,
a sigmoid router with a selection bias, a shared expert beside the held
routed ones) against the equations of its plain reference
(benchmarks/configs/kanana-2-30b-a3b_ref.py), at small sizes on the CPU in
float32 with seeded weights; the two-term attention kernel against plain
``jax.numpy``; the router's rules one by one; the shares of an expert-parallel
deployment against the uncut layer; the specs that are refused by name; and
the pins: with the grouped-query specs of the benchmark's two other language
models the traced programs are the parent commit's."""

import copy
import dataclasses
import hashlib
import importlib.util
import json
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import create_model
from fedml_tpu.models.decoder import BIAS_COUNTER, COUNTERS, counter_names, routed_experts
from fedml_tpu.ops.attention import attention, takes_kernel
from fedml_tpu.ops.flash_attention import flash_attention_bthd
from fedml_tpu.parallel.ring_attention import full_attention

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from benchmarks.lib import fedavg_ref  # noqa: E402

# Every mechanism of the published spec, small: 2 heads of (16 | 8) with
# values of 16 out of a latent of 24, one dense layer and two expert layers,
# top-2 of 8 sigmoid-scored experts by a biased choice, 4 of them held, one
# shared expert of twice an expert's width.
SPEC = dict(
    hidden_size=32, num_attention_heads=2, num_hidden_layers=3,
    kv_lora_rank=24, q_lora_rank=None, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=1000000, rope_interleave=True, rope_scaling=None,
    first_k_dense_replace=1, intermediate_size=48,
    n_routed_experts=8, n_shared_experts=2, num_experts_per_tok=2, moe_intermediate_size=12,
    scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1,
    norm_topk_prob=True, routed_scaling_factor=2.448, rms_norm_eps=1e-6,
    tie_word_embeddings=False, experts_held=[2, 6],
)
VOCAB, LENGTH = 61, 24
# The kernel's route: heads of whole lane tiles (the published 128 | 64 and
# values of 128) at the shortest length the kernel takes.
KERNEL_SPEC = dict(SPEC, hidden_size=64, num_hidden_layers=2, qk_nope_head_dim=128,
                   qk_rope_head_dim=64, v_head_dim=128)
KERNEL_LENGTH = 256


def reference():
    path = ROOT / "benchmarks" / "configs" / "kanana-2-30b-a3b_ref.py"
    spec = importlib.util.spec_from_file_location("kanana_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(spec, length=LENGTH):
    return {"model": {"name": "decoder", "dataset": "random_tokens", "input_shape": [length],
                      "num_classes": VOCAB, "kwargs": copy.deepcopy(spec)}}


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


ROUTES = {
    # spec, length, documents, whether the attention sites take the kernel
    "plain_route": (SPEC, LENGTH, 3, False),
    "rotate_half_layout": (dict(SPEC, rope_interleave=False), LENGTH, 2, False),
    "kernel_route_interpreted": (KERNEL_SPEC, KERNEL_LENGTH, 1, True),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_loss_and_every_gradient_match_the_reference(route):
    """(a) The whole model, latent layers included, forward and gradient.
    Both sides are exact float32 on the CPU and differ by the order of their
    sums (the reference de-interleaves the rotary dims as HF does, the
    program rotates the pairs where they lie): 2e-5 of a leaf's largest
    gradient, 1e-6 of the loss. The selection bias gets no gradient on
    either side."""
    spec, length, docs, kernel = ROUTES[route]
    ref, cfg = reference(), config(spec, length)
    model = create_model("decoder", "random_tokens", (length,), VOCAB, **cfg["model"]["kwargs"])
    assert all(takes_kernel(length, *site) is kernel for site in model.module.attention_sites())
    flat = ref.init_params(5, cfg)
    have = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert set(have) == {"params"}
    assert {k: v.shape for k, v in flatten(have["params"]).items()} == ref.param_shapes(cfg)
    doc = jax.random.randint(jax.random.PRNGKey(9), (docs, length + 1), 1, VOCAB)
    x, y = doc[:, :-1], doc[:, 1:]
    mask = jnp.ones((docs,), jnp.float32)

    def program_loss(flat):
        logits, _ = model.apply({"params": nest(flat)}, x, train=True)
        return fedavg_ref.task_loss("nwp", logits, y, mask)[0]

    def reference_loss(flat):
        return fedavg_ref.task_loss(
            "nwp", ref.logits_fn(flat, x, fedavg_ref.REFERENCE, cfg), y, mask)[0]

    loss_p, grad_p = jax.jit(jax.value_and_grad(program_loss))(flat)
    loss_r, grad_r = jax.jit(jax.value_and_grad(reference_loss))(flat)
    assert abs(float(loss_p) - float(loss_r)) <= 1e-6 * abs(float(loss_r))
    for name in grad_r:
        scale = float(jnp.max(jnp.abs(grad_r[name])))
        gap = float(jnp.max(jnp.abs(grad_p[name] - grad_r[name])))
        if name.endswith("router_bias"):
            assert scale == 0 and gap == 0, name
            continue
        assert scale > 0, name
        assert gap <= 2e-5 * scale, (name, gap, scale)


def two_term_plain(q, k, v, q_rope, k_rope, scale):
    """Latent attention's core in plain ``jax.numpy``, k_rope repeated."""
    T = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) + jnp.einsum(
        "bqhr,bkhr->bhqk", q_rope, jnp.repeat(k_rope, q.shape[2], axis=2))
    keep = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(keep, s * scale, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("form", ["kernel", "plain_form"])
@pytest.mark.parametrize("length", [256, 512])
def test_two_term_attention_matches_plain_numpy_with_the_shared_key_gradient_summed(length, form):
    """(b) Output and all five gradients, causal, heads of 128 | 64 with
    values of 128; dK_rope is the sum over the heads (the plain side repeats
    the key per head and lets autodiff sum). float32: the kernel's pins of
    tests/test_flash_attention.py."""
    B, H, D, R = 2, 3, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(length), 5)
    shapes = [(B, length, H, D)] * 3 + [(B, length, H, R), (B, length, 1, R)]
    args = [jax.random.normal(kk, s, jnp.float32) for kk, s in zip(ks, shapes)]
    scale = (D + R) ** -0.5
    assert takes_kernel(length, H, H, D, R, D)

    def through(fn):
        def loss(*a):
            out = fn(*a)
            return jnp.sum(jnp.sin(out)), out

        (_, out), grads = jax.jit(jax.value_and_grad(loss, argnums=range(5), has_aux=True))(*args)
        return (out,) + grads

    entry = flash_attention_bthd if form == "kernel" else full_attention
    got = through(lambda q, k, v, qr, kr: entry(
        q, k, v, causal=True, q_rope=qr, k_rope=kr, scale=scale))
    want = through(lambda *a: two_term_plain(*a, scale))
    assert got[5].shape == (B, length, 1, R)
    for name, a, b, tol in zip(("out", "dq", "dk", "dv", "dq_rope", "dk_rope"), got, want,
                               (2e-5, 5e-5, 5e-5, 5e-5, 5e-5, 5e-5)):
        gap = float(jnp.max(jnp.abs(a - b)))
        assert gap <= tol * max(1.0, float(jnp.max(jnp.abs(b)))), (name, gap)


@pytest.mark.parametrize("T,H,KV,D,R,V,takes", [
    (2048, 32, 32, 128, 64, 128, True),    # kanana-2-30b-a3b.silo2b1's training step
    (64, 32, 32, 128, 64, 128, False),     # its evaluation documents
    (2048, 32, 32, 128, 64, 64, False),    # values narrower than the keys
    (2048, 32, 32, 64, 32, 64, False),     # heads narrower than a lane tile
    (2048, 32, 4, 128, 64, 128, False),    # grouped keys beside a second term
    (8192, 32, 32, 128, 64, 128, False),
])
def test_the_latent_sites_decision_is_of_shapes_alone(T, H, KV, D, R, V, takes):
    assert takes_kernel(T, H, KV, D, R, V) is takes


def router_case(tokens=64, d=16, experts=16, f=8, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (tokens, d)), jax.random.normal(ks[1], (d, experts)),
            0.3 * jax.random.normal(ks[2], (experts, d, f)),
            0.3 * jax.random.normal(ks[3], (experts, d, f)),
            0.3 * jax.random.normal(ks[4], (experts, f, d)),
            0.5 * jax.random.normal(ks[5], (experts,)))


def dense_sigmoid(x, router, gate, up, down, bias, top_k, lo, hi, scale):
    """HF's router written densely: every held expert on every token,
    weighted by the slot that chose it."""
    s = jax.nn.sigmoid(x @ router)
    _, chosen = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    out = jnp.zeros_like(x)
    for e in range(lo, hi):
        weight = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1, keepdims=True)
        out = out + weight * ((jax.nn.silu(x @ gate[e]) * (x @ up[e])) @ down[e])
    return out


@pytest.mark.parametrize("held", [(0, 16), (4, 8)])
def test_sigmoid_router_chooses_by_the_biased_scores_and_weighs_by_the_scores(held):
    """(c) Choice by ``s + b``, weights ``s[chosen] / (sum + 1e-20) * 2.448``,
    value and gradients against the dense form; no gradient on the bias."""
    lo, hi = held
    x, router, gate, up, down, bias = router_case()

    def grouped(x, router, gate, up, down, bias):
        y, counters = routed_experts(
            x, router, gate[lo:hi], up[lo:hi], down[lo:hi], bias, top_k=3, held_from=lo,
            scoring="sigmoid", scale=2.448)
        return jnp.sum(jnp.sin(y)), counters

    def dense(x, router, gate, up, down, bias):
        return jnp.sum(jnp.sin(dense_sigmoid(x, router, gate, up, down, bias, 3, lo, hi, 2.448)))

    (vg, counters), gg = jax.value_and_grad(grouped, argnums=range(6), has_aux=True)(
        x, router, gate, up, down, bias)
    vd, gd = jax.value_and_grad(dense, argnums=range(6))(x, router, gate, up, down, bias)
    assert abs(float(vg - vd)) <= 1e-5 * abs(float(vd))
    for a, b in zip(gg[:5], gd[:5]):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7
    assert not np.asarray(gg[5]).any() and not np.asarray(gd[5]).any()
    c = dict(zip(counter_names(True), np.asarray(counters)))
    assert c["moe_dropped"] == 0 and 0 < c[BIAS_COUNTER] < 64 * 3


def test_the_bias_moves_choices_and_not_weights_and_the_counter_counts_them():
    """(c) With a zero bias nothing is moved and the layer is the unbiased
    one; a bias that lifts one expert over all moves exactly the pairs of the
    tokens that had not chosen it, and their weights stay scores."""
    x, router, gate, up, down, _ = router_case()
    rules = dict(top_k=3, scoring="sigmoid", scale=2.448)
    plain, c_plain = routed_experts(x, router, gate, up, down, **rules)
    zero, c_zero = routed_experts(x, router, gate, up, down, jnp.zeros((16,)), **rules)
    assert len(c_plain) == len(COUNTERS) and len(c_zero) == len(COUNTERS) + 1
    assert float(c_zero[-1]) == 0 and jnp.array_equal(plain, zero)
    s = jax.nn.sigmoid(x @ router)
    without = int(jnp.sum(jnp.all(jax.lax.top_k(s, 3)[1] != 5, axis=-1)))
    lifted, c_lifted = routed_experts(
        x, router, gate, up, down, jnp.zeros((16,)).at[5].set(10.0), **rules)
    assert 0 < without == float(c_lifted[-1])
    want = dense_sigmoid(x, router, gate, up, down, jnp.zeros((16,)).at[5].set(10.0),
                         3, 0, 16, 2.448)
    assert float(jnp.max(jnp.abs(lifted - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))


def test_softmax_rules_keep_the_program_they_had():
    """(c, e) With softmax, no bias and scale 1 the layer's jaxpr is the one
    it traces with the three new arguments left out."""
    x, router, gate, up, down, _ = router_case()
    before = jax.make_jaxpr(lambda *a: routed_experts(*a, top_k=3))(x, router, gate, up, down)
    now = jax.make_jaxpr(lambda *a: routed_experts(*a, None, top_k=3, scoring="softmax", scale=1.0))(
        x, router, gate, up, down)
    assert str(before) == str(now)


def test_the_sixteen_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """(d) model-configs guide, section 4: 32 experts over 16 chips, 2 each.
    Every chip computes the shared expert alike, so it is counted once; the
    16 routed parts and it add up to the layer with every expert held. 1e-5:
    the order of sixteen partial sums in float32."""
    spec = dict(SPEC, num_hidden_layers=1, first_k_dense_replace=0, n_routed_experts=32,
                num_experts_per_tok=4)
    whole = create_model("decoder", "random_tokens", (LENGTH,), VOCAB,
                         **dict(spec, experts_held=None))
    params = whole.init(jax.random.PRNGKey(2))["params"]
    layer = dict(params["layers_0"])
    layer["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (32,))
    n = jax.random.normal(jax.random.PRNGKey(4), (40, 32))
    experts = (layer["experts_gate"], layer["experts_up"], layer["experts_down"])
    shared = (jax.nn.silu(n @ layer["shared_gate"]) * (n @ layer["shared_up"])) @ layer["shared_down"]
    rules = dict(top_k=4, scoring="sigmoid", scale=2.448)

    uncut, counted = routed_experts(n, layer["router"], *experts, layer["router_bias"], **rules)
    parts, pairs = jnp.zeros_like(uncut), 0.0
    for chip in range(16):
        lo = 2 * chip
        y, c = routed_experts(n, layer["router"], *(w[lo:lo + 2] for w in experts),
                              layer["router_bias"], held_from=lo, **rules)
        parts, pairs = parts + y, pairs + float(c[0])
    assert pairs == float(counted[0]) == 40 * 4
    total, want = parts + shared, uncut + shared
    assert float(jnp.max(jnp.abs(total - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))
    # the same through the module's layer: with the experts' last product
    # zeroed a layer gives everything but its routed part (the residual, the
    # attention branch and the shared expert), so the 16 shares' layers less
    # 15 of that are the uncut layer: the shared expert once
    from fedml_tpu.models.decoder import DecoderLayer, rotary_tables

    m = whole.module
    x = jax.random.normal(jax.random.PRNGKey(5), (2, LENGTH, 32))
    tables = rotary_tables({"rope_theta": 1e6}, 8, LENGTH)

    def layer_out(held, weights):
        lo, hi = held
        cut = dict(weights, **{k: weights[k][lo:hi]
                               for k in ("experts_gate", "experts_up", "experts_down")})
        ffn = dataclasses.replace(m.feed_forwards()[0], held=held)
        block = DecoderLayer("full_attention", m.attention_spec(), ffn, 64, 1e-6)
        return block.apply({"params": cut}, x, *tables)

    rest = layer_out((0, 32), dict(layer, experts_down=jnp.zeros_like(layer["experts_down"])))
    summed = sum(layer_out((2 * chip, 2 * chip + 2), layer) for chip in range(16)) - 15 * rest
    want = layer_out((0, 32), layer)
    assert float(jnp.max(jnp.abs(summed - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("change,names", [
    (dict(q_lora_rank=1536), "q_lora_rank"),
    (dict(n_group=8, topk_group=4), "n_group"),
    (dict(scoring_func="tanh"), "scoring_func"),
    (dict(topk_method="group_limited_greedy"), "topk_method"),
    (dict(rope_scaling={"type": "yarn", "factor": 40}), "rope_scaling"),
    (dict(intermediate_size=None), "intermediate_size"),
])
def test_specs_that_are_not_expressed_are_refused_by_name(change, names):
    """(f) At ``create_model``, not at the first trace."""
    with pytest.raises(ValueError, match=names):
        create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **dict(SPEC, **change))


def test_create_model_reports_the_latent_sites_the_counters_and_the_constants():
    model = create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **SPEC)
    assert model.module.attention_sites() == ((2, 2, 16, 8, 16),) * 3
    assert model.counters == COUNTERS + (BIAS_COUNTER,)
    assert model.flush_attrs(1) == {"attn_kernel_sites": 0, "attn_sites": 3,
                                    "attn_qk_width": 24, "attn_v_width": 16, "attn_heads": 2,
                                    "attn_length": LENGTH, "attn_layers": 3,
                                    "moe_kernel_sites": 0, "moe_grouped_sites": 18,
                                    "moe_slot_kernel_sites": 0, "moe_slot_sites": 4,
                                    "hidden": 32, "expert_width": 12, "layers": 2,
                                    "expert_layers": 2, "top_k": 2, "expert_products": 3,
                                    "shared_width": 24}
    # gate, up and down of each expert layer over 64 tokens: top-2 of 8 with
    # 4 held bounds the rows at twice the even share, 128; 32 <-> 12
    assert model.module.grouped_sites(64) == ((128, 32, 12, 4),) * 2 + ((128, 12, 32, 4),) + (
        (128, 32, 12, 4),) * 2 + ((128, 12, 32, 4),)
    # grouped-query specs keep their sites, counters and constants
    mellum = create_model("decoder", "random_tokens", (LENGTH,), VOCAB, num_experts=4)
    assert mellum.module.attention_sites() == ((4, 2, 32),) * 2 and mellum.counters == COUNTERS
    assert mellum.flush_attrs(1)["layers"] == mellum.flush_attrs(1)["expert_layers"] == 2
    assert "shared_width" not in mellum.flush_attrs(1)


def test_the_sites_are_what_the_traced_layers_hand_the_attention_core(monkeypatch):
    """The small repair: ``attention_sites`` and ``DecoderLayer``'s calls are
    built from one ``AttentionSpec``; what reaches ``attention`` at trace
    time is the site, for both kinds of attention."""
    import fedml_tpu.models.decoder as decoder

    seen = []

    def spy(q, k, v, causal=False, window=None, q_rope=None, k_rope=None, scale=None):
        latent = () if q_rope is None else (q_rope.shape[-1], v.shape[-1])
        seen.append((q.shape[2], k.shape[2], q.shape[3]) + latent)
        return jnp.zeros(q.shape[:3] + (v.shape[-1],), q.dtype)

    monkeypatch.setattr(decoder, "attention", spy)
    for spec in (SPEC, dict(num_experts=4)):
        seen.clear()
        model = create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **spec)
        jax.eval_shape(model.init, jax.random.PRNGKey(0))
        assert tuple(seen) == model.module.attention_sites()


# --- (e) pins -----------------------------------------------------------------
# sha256 (16 hex digits) of the traced program's text with memory addresses and
# source locations taken out, computed at the parent commit 53c3b72 by this
# same function. A change that leaves the grouped-query programs alone keeps
# them; one that means to change them computes them anew (run this file's
# ``digest`` on the new tree) and says so. PR 35 meant to change Mellum's two:
# the expert layer keeps its bounded rows and is no ``jax.checkpoint`` (at
# its parent 30933ff they read 79cdf5cea5de352f and fbc1060337a8a788). PR 37
# meant to change Mellum's again: q and k of every layer go through
# ``ops/rotary.rotary``. At the cell's size that is the kernel and its own
# backward rule, and the pin is computed anew (at its parent a1e2524 it read
# 8308e4dd86564cb3); at the rehearsal's 32 positions the operator IS the plain
# three-line form, traced as before, so that pin was recomputed and came out
# as it was (2ef7c050eeac5d0a). Both attention steps and both GPT-2 pins hold.
# PR 38 left all six as they were (the one-part stack is a branch that no
# accepted spec takes) and added the two of the configuration it brought,
# computed on its own tree: the program its chip readings are of. PR 39 meant
# to change the two full-size programs with routed experts: their grouped
# products take ``ops/grouped_matmul``'s kernels (at its parent 4cc8847 they
# read ff35ffb062b3311d and 888d98eb5abd1cfe); the rehearsals' widths keep
# ``ragged_dot`` and their pins hold, as do the other four. PR 40 left all
# eight as they were (per-layer attention specs and the partial rotary's
# lanes trace as before where every layer has one shape and turns the whole
# head) and added the two of the configuration it brought. The Mamba-2 scan's
# kernels (``ops/ssd.takes_kernel``) meant to change Nemotron's full-size
# program: its three scans at 4 096 positions go to ``ssd_fwd`` / ``ssd_bwd``
# under a ``jax.custom_vjp`` (at the parent d2b87b9 it read eaa14cfb8b04d242);
# the rehearsal's chunks of 16 keep the chunked products and its pin holds,
# as do the other seven. The token-side sums meant to change every program
# with routed experts but GPT-2's: a slot reads its sorted row only where the
# row holds a held pair, and Mellum's 4 096 tokens a step take
# ``ops/slot_sum``'s kernel (at the parent 7ed97b7 the six read
# 7807aa87a686398f, 2ef7c050eeac5d0a, ca0b95984ccd4fb7, e7137c8985465d0e,
# 37291c2624a2a267 and 23cb7c783d5d8684); both GPT-2 pins hold. The SambaY
# stack (a Mamba-1 scan, differential attention over values twice the keys'
# width, LayerNorms) is a branch that no accepted spec takes: the ten pins
# hold, and LFM2's two (computed at the parent a80e48a by this same function)
# join them, so that every accepted program that shares the attention entry
# with the widened values is pinned.
PINS = {
    "attention.silo4": "940131b509805ea9",
    "attention.silo2": "50842107702d88df",
    "mellum2-12b-a2.5b.full": "047105c6ae2b0a8e",
    "mellum2-12b-a2.5b.rehearse": "e5325920fefbc11d",
    "gpt2-124m.full": "28bcf5bfcdd2a422",
    "gpt2-124m.rehearse": "8ce4b5786b6eb411",
    "nemotron-twotower-30b-a3b.full": "0c922e0526eee6ac",
    "nemotron-twotower-30b-a3b.rehearse": "897a09af8bb81a0f",
    "laguna-xs.2.full": "2bd3a81119a8c809",
    "laguna-xs.2.rehearse": "8fa8ca783b6b41b4",
    "lfm2-8b-a1b.full": "3f14deae87464d80",
    "lfm2-8b-a1b.rehearse": "4e6c8767908fc2a3",
}
ATTENTION_STEPS = {"attention.silo4": ((4, 1024, 12, 12, 64), None),
                   "attention.silo2": ((2, 2048, 32, 4, 128), 1024)}


def digest(jaxpr):
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    text = re.sub(r" at [^\s\]]+\.py:\d+", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(ATTENTION_STEPS))
def test_grouped_query_attention_traces_as_at_the_parent(name):
    """The attention entry, forward and gradient (both kernels and their
    wrappers), at the two accepted language-model cells' training step."""
    (B, T, H, KV, D), window = ATTENTION_STEPS[name]

    def loss(q, k, v):
        return jnp.sum(attention(q, k, v, causal=True, window=window).astype(jnp.float32))

    args = [jax.ShapeDtypeStruct((B, T, h, D), jnp.bfloat16) for h in (H, KV, KV)]
    assert digest(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(*args)) == PINS[name]


@pytest.mark.parametrize("name", sorted(set(PINS) - set(ATTENTION_STEPS)))
def test_the_accepted_language_models_trace_as_at_the_parent(name):
    """Loss-like scalar and every gradient of the model as the round program
    applies it (bfloat16 parameters, training mode, counters where it has
    them), from the benchmark's own configuration files, at the cell's size
    and at its rehearsal's. Shapes only: nothing is computed."""
    config_name, size = name.rsplit(".", 1)
    cfg = json.loads((ROOT / "benchmarks" / "configs" / f"{config_name}.json").read_text())
    m = cfg["model"] if size == "full" else cfg["rehearse"]["model"]
    model = create_model(m["name"], m["dataset"], tuple(m["input_shape"]), int(m["num_classes"]),
                         **m.get("kwargs", {}))
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2 if "mellum" in name else 4, m["input_shape"][0]), jnp.int32)

    def loss(variables, x):
        out = model.apply(variables, x, train=True, **({"counters": True} if model.counters else {}))
        return jnp.sum(jax.nn.log_softmax(out[0].astype(jnp.float32))[..., 0]), out[2:]

    assert digest(jax.make_jaxpr(jax.value_and_grad(loss, has_aux=True))(shapes, tokens)) == PINS[name]


# --- through FedAvgAPI.train() ------------------------------------------------


def one_round(mode):
    from fedml_tpu.algorithms import FedAvgAPI
    from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
    from fedml_tpu.data.base import FederatedDataset
    from fedml_tpu.telemetry import get_tracer

    length, per_client, clients = 32, 4, 3
    docs = np.random.default_rng(0).integers(
        1, VOCAB, size=(clients, per_client, length + 1), dtype=np.int32)
    data = FederatedDataset(
        name="random_tokens", client_x=list(docs[:, :, :-1]), client_y=list(docs[:, :, 1:]),
        test_x=docs[0, :2, :-1], test_y=docs[0, :2, 1:], num_classes=VOCAB)
    model = create_model("decoder", "random_tokens", (length,), VOCAB, **SPEC)
    seeded = dataclasses.replace(model)
    inner = model.init

    def init(rng):
        # a selection bias that moves choices: init leaves it at zero
        variables = inner(rng)
        for i in (1, 2):
            variables["params"][f"layers_{i}"]["router_bias"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(i), (8,))
        return variables

    seeded.init = init
    cfg = RunConfig(
        data=DataConfig(batch_size=2, pad_bucket=1),
        fed=FedConfig(client_num_in_total=clients, client_num_per_round=clients, comm_round=2,
                      epochs=1, frequency_of_the_test=1, client_parallelism=mode),
        train=TrainConfig(client_optimizer="sgd", lr=0.05), model="decoder", seed=3)
    rows, tracer = [], get_tracer()
    t0 = tracer.now_us()
    api = FedAvgAPI(cfg, data, seeded, task="nwp", log_fn=rows.append)
    bias0 = np.asarray(api.global_vars["params"]["layers_1"]["router_bias"])
    api.train()
    flushes = [e.attrs for e in tracer.events() if e.name == "flush" and e.ts_us >= t0]
    bias = np.asarray(api.global_vars["params"]["layers_1"]["router_bias"])
    return rows, flushes, flatten(api.global_vars["params"]), bias0, bias


def test_a_federated_round_is_the_same_under_vmap_and_scan_and_keeps_the_bias():
    """The latent decoder through ``FedAvgAPI.train()`` under both client
    schedules: the same parameters (float32, the order of sums aside), the
    expert counters with ``moe_bias_moved`` and the new constants on the
    ``flush`` span, and a selection bias that local training leaves as it
    came (no gradient) and the average returns to within its own rounding
    (three equal copies weighted by thirds)."""
    rows_v, flushes_v, params_v, bias0, bias_v = one_round("vmap")
    rows_s, flushes_s, params_s, _, bias_s = one_round("scan")
    assert bias0.any()
    assert np.allclose(bias0, bias_v, rtol=3e-7, atol=0) and np.allclose(bias0, bias_s, rtol=3e-7, atol=0)
    for name in params_v:
        assert jnp.allclose(params_v[name], params_s[name], rtol=0, atol=2e-6), name
    for flushes in (flushes_v, flushes_s):
        assert flushes and all(a["moe_dropped"] == 0 for a in flushes)
        a = flushes[0]
        assert a["layers"] == a["expert_layers"] == 2 and a["shared_width"] == 24
        assert (a["moe_grouped_sites"], a["moe_kernel_sites"]) == (18, 0)
        assert (a["attn_qk_width"], a["attn_v_width"], a["attn_heads"], a["attn_length"],
                a["attn_layers"], a["attn_sites"], a["attn_kernel_sites"]) == (24, 16, 2, 32, 3, 3, 0)
        # 2 rounds x 3 clients x 2 steps x 2 expert layers, top-2 of 2 x 32 tokens
        assert sum(a["moe_calls"] for a in flushes) == 24
        assert 0 < sum(a["moe_bias_moved"] for a in flushes) < 24 * 64 * 2
    assert sum(a["moe_bias_moved"] for a in flushes_v) == sum(
        a["moe_bias_moved"] for a in flushes_s)
    losses = [r["Train/Loss"] for r in rows_v if "Train/Loss" in r]
    assert len(losses) == 2 and losses[1] < losses[0]


def test_every_part_of_a_layer_is_a_scope_directly_under_it():
    """A device trace splits a layer by these names (``tools/anatomy.py``
    reads the two path parts after the model): no method's own scope may
    stand between the layer and them."""
    model = create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **SPEC)
    variables = model.init(jax.random.PRNGKey(0))
    text = jax.jit(lambda v, x: model.apply(v, x, train=True)[0]).lower(
        variables, jnp.ones((2, LENGTH), jnp.int32)).as_text(debug_info=True)
    under = {i: set(re.findall(rf"layers_{i}/([\w.]+)", text)) for i in (0, 1)}
    assert {"q_proj", "kv_latent", "rope", "attention_mla", "out", "mlp"} <= under[0]
    assert {"q_proj", "kv_latent", "rope", "attention_mla", "out", "shared",
            "router", "dispatch"} <= under[1]
    assert all(s in text for s in ("experts", "combine")) and "checkpoint" not in text
    assert not any("." in name for names in under.values() for name in names)
