"""The decoder's third block shape (layers whose token mixer is a gated short
convolution and no attention, QK-normed grouped-query attention, leading
dense layers and a bias-selected sigmoid router with 1e-6 in its
renormalisation) against the equations of its plain reference
(benchmarks/configs/lfm2-8b-a1b_ref.py), at small sizes on the CPU in
float32 with seeded weights; the QK norm against plain numpy; the four
shares of an expert-parallel deployment against the uncut reference's expert
layer; the sites and scopes of a mixed stack; the specs that are refused by
name; and one federated round under both client schedules."""

import copy
import dataclasses
import importlib.util
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import create_model
from fedml_tpu.models.decoder import (
    BIAS_COUNTER, COUNTERS, LAYER_KINDS, DecoderLayer, ExpertSpec, routed_experts, rotary_tables)
from fedml_tpu.ops.attention import takes_kernel

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from benchmarks.lib import fedavg_ref  # noqa: E402

# Every mechanism of the published spec, small, as the configuration's
# ``model.kwargs`` spells it (the decoder's keys for the source's
# ``num_dense_layers``, ``norm_eps`` and ``use_expert_bias``):
# conv + dense, attention + experts, conv + experts; 4 query heads on 2
# key/value heads of 8 with QK norms; top-2 of 8 sigmoid-scored experts by a
# biased choice, 4 of them held; a filter of 3 taps.
SPEC = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    layer_types=["conv", "full_attention", "conv"], first_k_dense_replace=1,
    intermediate_size=48, num_experts=8, num_experts_per_tok=2, moe_intermediate_size=12,
    norm_topk_prob=True, scoring_func="sigmoid", topk_method="noaux_tc", renorm_eps=1e-6,
    routed_scaling_factor=1, rms_norm_eps=1e-5, conv_L_cache=3, rope_theta=1000000,
    use_qk_norm=True, tie_word_embeddings=False, experts_held=[2, 6],
)
VOCAB, LENGTH = 61, 24
# The kernel's route: heads of 64 with two query heads a key/value head (the
# published 32 on 8 in small), at the shortest length the kernel takes.
KERNEL_SPEC = dict(SPEC, hidden_size=64, head_dim=64, layer_types=["full_attention", "conv"],
                   first_k_dense_replace=0)
KERNEL_LENGTH = 256


def reference():
    path = ROOT / "benchmarks" / "configs" / "lfm2-8b-a1b_ref.py"
    spec = importlib.util.spec_from_file_location("lfm2_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(spec, length=LENGTH):
    return {"model": {"name": "decoder", "dataset": "random_tokens", "input_shape": [length],
                      "num_classes": VOCAB, "kwargs": copy.deepcopy(spec)}}


def build(spec, length=LENGTH):
    return create_model("decoder", "random_tokens", (length,), VOCAB, **copy.deepcopy(spec))


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
    # spec, length, documents, whether the attention site takes the kernel
    "plain_route": (SPEC, LENGTH, 3, False),
    "four_taps_two_dense_layers": (
        dict(SPEC, conv_L_cache=4, first_k_dense_replace=2), LENGTH, 2, False),
    "no_expert_bias_every_expert_held": (
        dict(SPEC, topk_method="greedy", experts_held=None), LENGTH, 2, False),
    "shorter_than_the_filter": (SPEC, 2, 4, False),
    "kernel_route_interpreted": (KERNEL_SPEC, KERNEL_LENGTH, 1, True),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_loss_and_every_gradient_match_the_reference(route):
    """The whole model, forward and gradient. Both sides are exact float32 on
    the CPU and differ by the order of their sums (the reference pads ``u``
    once and adds three slices, writes the scores one key/value head at a
    time and scatter-adds the experts' rows; the program shifts, calls the
    attention core once and gathers): 2e-5 of a leaf's largest gradient,
    1e-6 of the loss, the latent decoder's tolerances. The selection bias
    gets no gradient on either side."""
    spec, length, docs, kernel = ROUTES[route]
    ref, cfg = reference(), config(spec, length)
    model = build(spec, length)
    assert len(model.module.attention_sites()) == spec["layer_types"].count("full_attention")
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


def test_the_module_draws_the_filter_at_one_over_root_taps_and_the_reference_too():
    """A unit-gain sum over the taps on both sides: deviation 1/sqrt(3), not
    the 0.02 of the projections."""
    spec = dict(SPEC, hidden_size=256, layer_types=["conv"], first_k_dense_replace=1)
    drawn = jax.jit(build(spec).init)(jax.random.PRNGKey(1))["params"]["layers_0"]["conv"]
    seeded = reference().init_params(3, config(spec))["layers_0/conv"]
    for w in (drawn, seeded):
        assert w.shape == (256, 3) and abs(float(jnp.std(w)) - 3 ** -0.5) < 0.05


def test_qk_norm_is_an_rms_norm_over_each_heads_dims_ahead_of_rotary():
    """What reaches the attention core, against plain numpy: q and k divided
    by the root mean square over each head's 8 dims (eps ``rms_norm_eps``), times
    ONE learned scale of 8 for all heads of q and one for k, then rotated;
    v as projected. float32 on both sides: 1e-5."""
    import fedml_tpu.models.decoder as decoder

    spec = dict(SPEC, layer_types=["full_attention"], first_k_dense_replace=0)
    model = build(spec)
    params = jax.jit(model.init)(jax.random.PRNGKey(2))["params"]
    layer = dict(params["layers_0"])
    rng = np.random.default_rng(0)
    layer["q_layernorm"] = {"scale": jnp.asarray(rng.uniform(0.5, 1.5, 8), jnp.float32)}
    layer["k_layernorm"] = {"scale": jnp.asarray(rng.uniform(0.5, 1.5, 8), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(2, LENGTH, 32)), jnp.float32)
    seen = {}

    def spy(q, k, v, causal=False, window=None, q_rope=None, k_rope=None, scale=None):
        seen.update(q=q, k=k, v=v)
        return jnp.zeros_like(q)

    block = DecoderLayer("full_attention", model.module.attention_spec(),
                         model.module.feed_forwards()[0], 64, 1e-5)
    cos, sin = rotary_tables({"rope_theta": 1000000}, 8, LENGTH)
    saved, decoder.attention = decoder.attention, spy
    try:
        # traced once, so ``seen`` holds tracers' values only through the output
        seen_out = jax.jit(lambda p, x: (block.apply({"params": p}, x, cos, sin), dict(seen))[1])(
            layer, x)
    finally:
        decoder.attention = saved
    seen = seen_out

    n = np.asarray(x, np.float64)
    n = n / np.sqrt(np.mean(n * n, axis=-1, keepdims=True) + 1e-5)
    n = n * np.asarray(layer["input_layernorm"]["scale"], np.float64)

    def normed_and_turned(w, heads, scale):
        h = (n @ np.asarray(w, np.float64)).reshape(2, LENGTH, heads, 8)
        h = h / np.sqrt(np.mean(h * h, axis=-1, keepdims=True) + 1e-5) * np.asarray(scale, np.float64)
        turned = np.concatenate([-h[..., 4:], h[..., :4]], axis=-1)
        c, s = np.asarray(cos, np.float64)[None, :, None], np.asarray(sin, np.float64)[None, :, None]
        return h * c + turned * s

    want_q = normed_and_turned(layer["q_proj"], 4, layer["q_layernorm"]["scale"])
    want_k = normed_and_turned(layer["k_proj"], 2, layer["k_layernorm"]["scale"])
    want_v = (n @ np.asarray(layer["v_proj"], np.float64)).reshape(2, LENGTH, 2, 8)
    for got, want in ((seen["q"], want_q), (seen["k"], want_k), (seen["v"], want_v)):
        assert float(np.max(np.abs(np.asarray(got) - want))) <= 1e-5 * float(np.max(np.abs(want)))
    # without the key the layer has no such leaves and hands over unnormed heads
    bare = jax.eval_shape(
        build(dict(spec, use_qk_norm=False)).init, jax.random.PRNGKey(2))["params"]["layers_0"]
    assert "q_layernorm" in layer and "q_layernorm" not in bare and "k_layernorm" not in bare


def test_the_four_shares_add_up_to_the_uncut_references_expert_layer():
    """model-configs guide, section 4: 32 experts over 4 chips, experts 0-7,
    8-15, 16-23 and 24-31. The program's four shares (sigmoid scores, the
    choice by scores + bias, weights over ``sum + 1e-6``, scale 1) add up to
    the REFERENCE's layer with every expert held, and their held pairs to
    tokens x top-4; there is no shared expert to count once. 1e-5: four
    partial sums in float32 against one scatter-add."""
    ref = reference()
    spec = dict(SPEC, num_experts=32, num_experts_per_tok=4, experts_held=None)
    s = ref._spec(config(spec))
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    n = jax.random.normal(ks[0], (40, 32))
    p = {"L/router": jax.random.normal(ks[1], (32, 32)),
         "L/router_bias": 0.05 * jax.random.normal(ks[2], (32,)),
         "L/experts_gate": 0.3 * jax.random.normal(ks[3], (32, 32, 12)),
         "L/experts_up": 0.3 * jax.random.normal(ks[4], (32, 32, 12)),
         "L/experts_down": 0.3 * jax.random.normal(ks[5], (32, 12, 32))}
    uncut = jax.jit(lambda n, p: ref._routed(n, p, "L/", s, fedavg_ref.REFERENCE))(n, p)
    rules = dict(top_k=4, scoring="sigmoid", scale=1.0, renorm_eps=1e-6)

    def share(lo):
        return jax.jit(lambda n, p: routed_experts(
            n, p["L/router"], *(p[f"L/experts_{w}"][lo:lo + 8] for w in ("gate", "up", "down")),
            p["L/router_bias"], held_from=lo, **rules))(n, p)

    parts, pairs, moved = jnp.zeros_like(uncut), 0.0, set()
    for lo in (0, 8, 16, 24):
        y, c = share(lo)
        parts, pairs = parts + y, pairs + float(c[0])
        moved.add(float(c[-1]))
        assert float(c[1]) == 0          # no held pair left outside the groups
    assert pairs == 40 * 4 and len(moved) == 1 and 0 < moved.pop() < 40 * 4
    assert float(jnp.max(jnp.abs(parts - uncut))) <= 1e-5 * float(jnp.max(jnp.abs(uncut)))
    # and one share is what the reference computes when it is given that share
    held = dict(s, held=(8, 16))
    cut = {k: (v[8:16] if k.startswith("L/experts") else v) for k, v in p.items()}
    y, _ = share(8)
    want = jax.jit(lambda n, p: ref._routed(n, p, "L/", held, fedavg_ref.REFERENCE))(n, cut)
    assert float(jnp.max(jnp.abs(y - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("spec,scoring,biased,eps", [
    (dict(SPEC), "sigmoid", True, 1e-6),
    (dict(SPEC, topk_method="greedy"), "sigmoid", False, 1e-6),
    # without the key the two accepted routers keep their rules:
    # DeepSeek-style sigmoid adds 1e-20, softmax nothing
    (dict(num_experts=4, scoring_func="sigmoid", topk_method="noaux_tc"), "sigmoid", True, 1e-20),
    (dict(num_experts=4), "softmax", False, 0.0),
])
def test_each_router_spec_gives_its_own_rules(spec, scoring, biased, eps):
    experts = [f for f in build(spec).module.feed_forwards() if isinstance(f, ExpertSpec)]
    assert experts and all(
        (e.scoring, e.biased, e.renorm_eps) == (scoring, biased, eps) for e in experts)


def test_the_renormalisations_epsilon_is_in_the_weights():
    """``w = s[chosen] / (sum + eps)``: with one expert a token the weight is
    ``s / (s + eps)``, which a large epsilon shows."""
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    router = jax.random.normal(jax.random.PRNGKey(1), (8, 4))
    ones = jnp.ones((4, 8, 8)) / 8
    rules = dict(top_k=1, scoring="sigmoid")
    plain, _ = jax.jit(lambda x: routed_experts(x, router, ones, ones, ones, **rules))(x)
    damped, _ = jax.jit(
        lambda x: routed_experts(x, router, ones, ones, ones, renorm_eps=1.0, **rules))(x)
    s = jnp.max(jax.nn.sigmoid(x @ router), axis=-1, keepdims=True)
    assert jnp.allclose(damped, plain * s / (s + 1.0), rtol=1e-5, atol=1e-7)


def test_the_norms_epsilon_and_the_rotary_base_reach_the_layers():
    """``rms_norm_eps`` is every norm's epsilon (the QK norms' too) and
    ``rope_theta`` the attention layers' base without a ``rope_parameters``."""
    flat = reference().init_params(2, config(SPEC))
    x = jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 1, VOCAB)

    def logits(m):
        return jax.jit(lambda flat: m.apply({"params": nest(flat)}, x, train=False)[0])(flat)

    model = build(SPEC)
    assert [isinstance(f, int) for f in model.module.feed_forwards()] == [True, False, False]
    for change in (dict(rms_norm_eps=1e-2), dict(rope_theta=100.0)):
        other = logits(build(dict(SPEC, **change)))
        assert not jnp.allclose(logits(model), other, atol=1e-6), change


@pytest.mark.parametrize("change,names", [
    (dict(conv_L_cache=None), "conv_L_cache"),
    (dict(conv_L_cache=0), "conv_L_cache"),
    (dict(layer_types=["conv", "linear_attention"]), "linear_attention"),
    (dict(scoring_func="tanh"), "scoring_func"),
    (dict(topk_method="group_limited_greedy"), "topk_method"),
    (dict(n_group=2), "n_group"),
    (dict(intermediate_size=None), "intermediate_size"),
    (dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8), "use_qk_norm"),
])
def test_specs_that_are_not_expressed_are_refused_by_name(change, names):
    """At ``create_model``, not at the first trace."""
    with pytest.raises(ValueError, match=names):
        build(dict(SPEC, **change))


def test_a_key_the_decoder_does_not_have_is_refused_by_its_name():
    """HF's ``conv_bias`` (a convolution with a bias is not expressed here)
    fails at ``create_model`` as any unknown key does."""
    with pytest.raises(TypeError, match="conv_bias"):
        build(dict(SPEC, conv_bias=True))


def test_the_filters_length_is_asked_only_where_a_layer_is_a_convolution():
    assert "conv" in LAYER_KINDS
    model = build(dict(SPEC, layer_types=["full_attention"], first_k_dense_replace=0,
                       conv_L_cache=None))
    assert "conv_layers" not in model.flush_attrs(1) and len(model.module.attention_sites()) == 1


def test_create_model_reports_sites_for_attention_layers_only_and_the_conv_constants():
    model = build(SPEC)
    assert model.module.attention_sites() == ((4, 2, 8),)
    assert model.counters == COUNTERS + (BIAS_COUNTER,)
    assert model.flush_attrs(1) == {"attn_kernel_sites": 0, "attn_sites": 1,
                                    "rope_kernel_sites": 0, "rope_sites": 2,
                                    "moe_kernel_sites": 0, "moe_grouped_sites": 18,
                                    "moe_slot_kernel_sites": 0, "moe_slot_sites": 4,
                                    "hidden": 32, "expert_width": 12, "layers": 2,
                                    "expert_layers": 2, "top_k": 2, "expert_products": 3,
                                    "conv_layers": 2, "conv_width": 32,
                                    "embed_kernel_sites": 0, "embed_sites": 1}
    only_conv = build(dict(SPEC, layer_types=["conv", "conv"], first_k_dense_replace=2))
    assert only_conv.module.attention_sites() == () and only_conv.counters == ()
    assert only_conv.flush_attrs(1) == {"conv_layers": 2, "conv_width": 32,
                                        "embed_kernel_sites": 0, "embed_sites": 1}
    # the accepted specs keep a site a layer and carry no conv constants
    mellum = create_model("decoder", "random_tokens", (LENGTH,), VOCAB, num_experts=4)
    assert mellum.module.attention_sites() == ((4, 2, 32),) * 2
    assert "conv_layers" not in mellum.flush_attrs(1)


def test_the_sites_are_what_the_traced_layers_hand_the_attention_core(monkeypatch):
    """A mixed stack: the attention core is called once for each attention
    layer and never by a conv layer, with the shapes ``attention_sites``
    reports."""
    import fedml_tpu.models.decoder as decoder

    seen = []

    def spy(q, k, v, causal=False, window=None, q_rope=None, k_rope=None, scale=None):
        seen.append((q.shape[2], k.shape[2], q.shape[3]))
        return jnp.zeros(q.shape[:3] + (v.shape[-1],), q.dtype)

    monkeypatch.setattr(decoder, "attention", spy)
    for kinds in (SPEC["layer_types"], ["conv", "conv"],
                  ["full_attention", "conv", "sliding_attention", "conv"]):
        seen.clear()
        model = build(dict(SPEC, layer_types=kinds, first_k_dense_replace=0))
        jax.eval_shape(model.init, jax.random.PRNGKey(0))
        assert tuple(seen) == model.module.attention_sites()
        assert len(seen) == len(kinds) - kinds.count("conv")


def test_every_part_of_a_conv_layer_is_a_scope_directly_under_it():
    """A device trace splits a layer by these names (``tools/anatomy.py``
    reads the two path parts after the model): ``in_proj``, ``short_conv``
    and ``out`` in a conv layer, ``qk_norm`` between ``qkv`` and ``rope`` in
    an attention layer; no method's own scope stands between the layer and
    them, in the forward or the backward pass."""
    model = build(SPEC)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def loss(v, x):
        return jnp.sum(model.apply(v, x, train=True)[0])

    text = jax.jit(jax.grad(loss)).lower(
        variables, jax.ShapeDtypeStruct((2, LENGTH), jnp.int32)).as_text(debug_info=True)
    under = {i: set(re.findall(rf"layers_{i}/([\w.]+)", text)) for i in (0, 1, 2)}
    assert {"in_proj", "short_conv", "out", "mlp"} <= under[0]
    assert {"qkv", "qk_norm", "rope", "attention_full", "out", "router", "dispatch"} <= under[1]
    assert {"in_proj", "short_conv", "out", "router", "dispatch"} <= under[2]
    assert not {"qkv", "rope", "attention_full", "qk_norm"} & (under[0] | under[2])
    assert not {"in_proj", "short_conv"} & under[1]
    assert not any("." in name for names in under.values() for name in names)
    # the scores are normed before they are rotated
    order = [text.index(f"layers_1/{s}") for s in ("qkv", "qk_norm", "rope", "attention_full")]
    assert order == sorted(order)


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
    model = build(SPEC, length)
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
    """The conv decoder through ``FedAvgAPI.train()`` under both client
    schedules: the same parameters (float32, the order of sums aside: the
    latent decoder's 2e-6), the conv layers' constants and one attention
    site of three layers on the ``flush`` span, the expert counters summed
    over the two expert layers, and a selection bias
    that local training leaves as it came (no gradient) and the average
    returns to within its own rounding (three equal copies weighted by
    thirds)."""
    rows_v, flushes_v, params_v, bias0, bias_v = one_round("vmap")
    rows_s, flushes_s, params_s, _, bias_s = one_round("scan")
    assert bias0.any()
    assert np.allclose(bias0, bias_v, rtol=3e-7, atol=0) and np.allclose(bias0, bias_s, rtol=3e-7, atol=0)
    for name in params_v:
        assert jnp.allclose(params_v[name], params_s[name], rtol=0, atol=2e-6), name
    for flushes in (flushes_v, flushes_s):
        assert flushes and all(a["moe_dropped"] == 0 for a in flushes)
        a = flushes[0]
        assert (a["conv_layers"], a["conv_width"]) == (2, 32)
        assert (a["attn_sites"], a["attn_kernel_sites"]) == (1, 0)
        assert a["layers"] == a["expert_layers"] == 2 and "shared_width" not in a
        assert (a["moe_grouped_sites"], a["moe_kernel_sites"]) == (18, 0)
        assert "attn_qk_width" not in a
        # 2 rounds x 3 clients x 2 steps x 2 expert layers, top-2 of 2 x 32 tokens
        assert sum(a["moe_calls"] for a in flushes) == 24
        assert 0 < sum(a["moe_bias_moved"] for a in flushes) < 24 * 64 * 2
    assert sum(a["moe_bias_moved"] for a in flushes_v) == sum(
        a["moe_bias_moved"] for a in flushes_s)
    losses = [r["Train/Loss"] for r in rows_v if "Train/Loss" in r]
    assert len(losses) == 2 and losses[1] < losses[0]
