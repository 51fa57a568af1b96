"""The decoder's fourth block shape (a stack of ONE part a layer under one
norm: Mamba-2 state-space mixers, attention without positions, ungated
ReLU-squared experts with a shared expert at its own width, dense MLPs)
against the equations of its plain reference
(benchmarks/configs/nemotron-twotower-30b-a3b_ref.py), at small sizes on the
CPU in float32 with seeded weights; the gated group norm against plain numpy;
the ungated expert layer against a dense loop over experts; the sixteen-way
shares of an expert-parallel deployment against the uncut reference's expert
part; sites, scopes and span constants; the specs that are refused by name;
and one federated round under both client schedules."""

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
    BIAS_COUNTER, COUNTERS, PARTS, DecoderLayer, MambaSpec, routed_experts)
from fedml_tpu.ops import ssd
from fedml_tpu.ops.attention import takes_kernel

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from benchmarks.lib import fedavg_ref  # noqa: E402

# Every mechanism of the published spec, small, as the configuration's
# ``model.kwargs`` spells it: M E M * E; 8 state-space heads of 8 in 2 groups
# with a state of 16, 4 taps, chunks of 16; 4 query heads on 2 key/value heads
# of 8 without rotary; top-2 of 8 sigmoid-scored experts by a biased choice,
# 4 of them held, ungated, scale 2.5; one shared expert at its own width.
SPEC = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    hybrid_override_pattern="MEM*E", n_routed_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=12, moe_shared_expert_intermediate_size=20, n_shared_experts=1,
    norm_topk_prob=True, scoring_func="sigmoid", topk_method="noaux_tc",
    routed_scaling_factor=2.5, rms_norm_eps=1e-5, mlp_hidden_act="relu2",
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16, conv_kernel=4,
    chunk_size=16, use_conv_bias=True, mamba_hidden_act="silu", time_step_limit=[0, None],
    time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
    tie_word_embeddings=False, experts_held=[2, 6],
)
VOCAB, LENGTH = 61, 40
# The kernel's route: heads of 128, two query heads a key/value head, at the
# shortest length the kernel takes (two chunks of the scan at 128).
KERNEL_SPEC = dict(SPEC, hidden_size=64, head_dim=128, num_attention_heads=2,
                   num_key_value_heads=1, hybrid_override_pattern="M*", chunk_size=128)
KERNEL_LENGTH = 256
SCAN_KERNEL_SPEC = dict(KERNEL_SPEC, mamba_num_heads=4, mamba_head_dim=64, ssm_state_size=128)


def reference():
    path = ROOT / "benchmarks" / "configs" / "nemotron-twotower-30b-a3b_ref.py"
    spec = importlib.util.spec_from_file_location("nemotron_ref", path)
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
    "a_dense_part_no_conv_bias_every_expert_held": (
        dict(SPEC, hybrid_override_pattern="M-E*", intermediate_size=24, use_conv_bias=False,
             experts_held=None), LENGTH, 2, False),
    "under_the_chunk_and_the_filter": (SPEC, 3, 4, False),
    "kernel_route_interpreted": (KERNEL_SPEC, KERNEL_LENGTH, 1, True),
    # the scan's kernels too: 4 state-space heads of 64 in 2 groups, state 128
    "scan_kernels_interpreted": (SCAN_KERNEL_SPEC, KERNEL_LENGTH, 1, True),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_loss_and_every_gradient_match_the_reference(route):
    """The whole model, forward and gradient. Both sides are exact float32 on
    the CPU and differ by the order of their sums: the reference runs the
    state-space recurrence position by position where the program runs it by
    chunks (``tests/test_ssd.py``: 5e-5 for the operator alone), pads the
    convolution's input once and adds slices, writes the scores a few heads
    at a time and scatter-adds the experts' rows. 1e-4 of a leaf's largest
    gradient (the chunked scan's order of sums, through four layers), 2e-6
    of the loss. The selection bias gets no gradient on either side."""
    spec, length, docs, kernel = ROUTES[route]
    ref, cfg = reference(), config(spec, length)
    model = build(spec, length)
    assert len(model.module.attention_sites()) == spec["hybrid_override_pattern"].count("*")
    assert all(takes_kernel(length, *site) is kernel for site in model.module.attention_sites())
    scan = model.flush_attrs(1)
    assert ssd.takes_kernel(length, *(scan["ssm_" + k] for k in (
        "heads", "head_dim", "groups", "state", "chunk"))) is (spec is SCAN_KERNEL_SPEC)
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
    assert abs(float(loss_p) - float(loss_r)) <= 2e-6 * abs(float(loss_r))
    for name in grad_r:
        scale = float(jnp.max(jnp.abs(grad_r[name])))
        gap = float(jnp.max(jnp.abs(grad_p[name] - grad_r[name])))
        if name.endswith("router_bias"):
            assert scale == 0 and gap == 0, name
            continue
        assert scale > 0, name
        assert gap <= 1e-4 * scale, (name, gap, scale)


def test_the_module_and_the_reference_draw_the_state_space_leaves_as_the_source_does():
    """``A_log = log(a)``, a uniform in [1, 16]; ``softplus(dt_bias)`` a step
    in [time_step_min, time_step_max] (log-uniform, floored at 1e-4); ``D``
    and the gated norm's scale ones; the filter at deviation 1/sqrt(taps),
    its bias zero."""
    spec = dict(SPEC, hybrid_override_pattern="M", mamba_num_heads=64, n_groups=8)
    drawn = jax.jit(build(spec).init)(jax.random.PRNGKey(1))["params"]["layers_0"]
    seeded = {k.split("/", 1)[1]: v for k, v in reference().init_params(3, config(spec)).items()
              if k.startswith("layers_0/")}
    for p in (drawn, seeded):
        a, step = np.exp(np.asarray(p["A_log"])), np.log1p(np.exp(np.asarray(p["dt_bias"])))
        assert a.shape == (64,) and a.min() >= 1 and a.max() <= 16 and a.max() - a.min() > 8
        assert step.min() >= 0.00099 and step.max() <= 0.1001 and step.max() > 10 * step.min()
        assert np.all(np.asarray(p["D"]) == 1) and np.all(np.asarray(p["gated_norm"]) == 1)
        assert np.all(np.asarray(p["conv_bias"]) == 0)
        assert p["conv"].shape == (64 * 8 + 2 * 8 * 16, 4)
        assert abs(float(jnp.std(p["conv"])) - 0.5) < 0.05


def test_the_gated_group_norm_gates_first_and_norms_each_group():
    """What reaches the output projection, against plain numpy: ``y *
    SiLU(z)``, divided by the root mean square over each group of inner / G
    numbers (eps ``rms_norm_eps``), times the learned scale. The scan is
    spied out (it returns its ``x``), so ``y`` is the convolution's ``x``.
    float32 on both sides: 1e-5."""
    import fedml_tpu.models.decoder as decoder

    model = build(dict(SPEC, hybrid_override_pattern="M"))
    layer = dict(jax.jit(model.init)(jax.random.PRNGKey(2))["params"]["layers_0"])
    rng = np.random.default_rng(0)
    layer["gated_norm"] = jnp.asarray(rng.uniform(0.5, 1.5, 64), jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, LENGTH, 32)), jnp.float32)
    ssm = model.module.mamba_spec()
    assert ssm == MambaSpec(8, 8, 2, 16, 4, 16) and (ssm.inner, ssm.conv_width) == (64, 128)
    block = DecoderLayer("mamba", model.module.attention_spec(), None, 64, 1e-5, 0, ssm)
    seen = {}

    def spy(x, dt, A, B, C, D, chunk):
        seen.update(x=x, dt=dt, A=A)
        return x

    saved, decoder.ssd = decoder.ssd, spy
    try:
        out, seen = jax.jit(
            lambda p, x: (block.apply({"params": p}, x, None, None), dict(seen)))(layer, x)
    finally:
        decoder.ssd = saved

    n = np.asarray(x, np.float64)
    n = n / np.sqrt(np.mean(n * n, axis=-1, keepdims=True) + 1e-5)
    zxbcdt = n @ np.asarray(layer["in_proj"], np.float64)
    z, dt = zxbcdt[..., :64], zxbcdt[..., 64 + 128:]
    y = np.asarray(seen["x"], np.float64).reshape(2, LENGTH, 64)
    g = (y * z / (1 + np.exp(-z))).reshape(2, LENGTH, 2, 32)
    g = g / np.sqrt(np.mean(g * g, axis=-1, keepdims=True) + 1e-5)
    want = np.asarray(x, np.float64) + (
        g.reshape(2, LENGTH, 64) * np.asarray(layer["gated_norm"], np.float64)
    ) @ np.asarray(layer["out_proj"], np.float64)
    assert float(np.max(np.abs(np.asarray(out) - want))) <= 1e-5 * float(np.max(np.abs(want)))
    # the step is softplus(dt + dt_bias) with no clamp, and A = -exp(A_log)
    step = np.log1p(np.exp(dt + np.asarray(layer["dt_bias"], np.float64)))
    assert float(np.max(np.abs(np.asarray(seen["dt"]) - step))) <= 1e-5 * float(step.max())
    assert np.allclose(np.asarray(seen["A"]), -np.exp(np.asarray(layer["A_log"])), rtol=1e-6)


def expert_weights(experts, d=32, f=12, seed=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return {"L/router": jax.random.normal(ks[1], (d, experts)),
            "L/router_bias": 0.05 * jax.random.normal(ks[2], (experts,)),
            "L/experts_up": 0.3 * jax.random.normal(ks[3], (experts, d, f)),
            "L/experts_down": 0.3 * jax.random.normal(ks[4], (experts, f, d)),
            "L/shared_up": 0.3 * jax.random.normal(ks[5], (d, 20)),
            "L/shared_down": 0.3 * jax.random.normal(ks[6], (20, d))}, jax.random.normal(ks[0], (40, d))


def test_the_ungated_expert_layer_against_a_dense_loop_over_experts():
    """``sum over the chosen slots of w_slot relu(n W_up_e)**2 W_down_e`` with
    every expert run on every token and the unchosen masked out: top-2 of 8
    by ``sigmoid + bias``, weights ``s / (sum + 1e-20) * 2.5``. 1e-5."""
    p, n = expert_weights(8)
    y, counters = jax.jit(lambda n, p: routed_experts(
        n, p["L/router"], None, p["L/experts_up"], p["L/experts_down"], p["L/router_bias"],
        top_k=2, scoring="sigmoid", scale=2.5, renorm_eps=1e-20))(n, p)
    s = jax.nn.sigmoid(n @ p["L/router"])
    _, chosen = jax.lax.top_k(s + p["L/router_bias"], 2)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = 2.5 * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    want = jnp.zeros_like(n)
    for e in range(8):
        weight = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1, keepdims=True)
        want = want + weight * (jnp.square(jax.nn.relu(n @ p["L/experts_up"][e])) @ p["L/experts_down"][e])
    assert float(jnp.max(jnp.abs(y - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))
    assert float(counters[0]) == 40 * 2 and float(counters[1]) == 0


def test_the_sixteen_shares_add_up_to_the_uncut_references_expert_part():
    """model-configs guide, section 4: 128 experts over 16 chips, experts 0-7,
    8-15, ... The program's sixteen shares of the routed sum (sigmoid scores,
    the choice by scores + bias, weights over ``sum + 1e-20``, scale 2.5,
    ungated experts) and the shared expert, which every chip computes alike,
    COUNTED ONCE, add up to the REFERENCE's expert part with every expert
    held; their held pairs add up to tokens x top-6. 1e-5: sixteen partial
    sums in float32 against one scatter-add."""
    ref = reference()
    spec = dict(SPEC, n_routed_experts=128, num_experts_per_tok=6, experts_held=None)
    s = ref._spec(config(spec))
    p, n = expert_weights(128)
    n3 = n[None]
    uncut = jax.jit(lambda n, p: ref._experts(n, p, "L/", s, fedavg_ref.REFERENCE))(n3, p)[0]
    shared = jax.jit(lambda n, p: ref._relu2(
        fedavg_ref.REFERENCE, n, p["L/shared_up"], p["L/shared_down"]))(n, p)
    rules = dict(top_k=6, scoring="sigmoid", scale=2.5, renorm_eps=1e-20)

    @jax.jit
    def share(n, p, lo):
        up = jax.lax.dynamic_slice_in_dim(p["L/experts_up"], lo, 8)
        down = jax.lax.dynamic_slice_in_dim(p["L/experts_down"], lo, 8)
        # held_from is a static number of the layer: routed on local ids
        rolled = {k: jnp.roll(p[k], -lo, axis=-1) for k in ("L/router", "L/router_bias")}
        return routed_experts(n, rolled["L/router"], None, up, down, rolled["L/router_bias"],
                              held_from=0, **rules)

    parts, pairs = shared, 0.0
    for lo in range(0, 128, 8):
        y, c = share(n, p, lo)
        parts, pairs = parts + y, pairs + float(c[0])
        assert float(c[1]) == 0          # no held pair left outside the groups
    assert pairs == 40 * 6
    assert float(jnp.max(jnp.abs(parts - uncut))) <= 1e-5 * float(jnp.max(jnp.abs(uncut)))
    # and one share, by its own held_from, is what the reference computes when
    # it is given that share
    held = dict(s, held=(8, 16))
    cut = {k: (v[8:16] if k.startswith("L/experts") else v) for k, v in p.items()}
    y, _ = jax.jit(lambda n, p: routed_experts(
        n, p["L/router"], None, p["L/experts_up"], p["L/experts_down"], p["L/router_bias"],
        held_from=8, **rules))(n, cut)
    want = jax.jit(lambda n, p: ref._routed(n, p, "L/", held, fedavg_ref.REFERENCE))(n, cut)
    assert float(jnp.max(jnp.abs(y - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("change,names", [
    (dict(hybrid_override_pattern="MEX*"), "hybrid_override_pattern.*X"),
    (dict(hybrid_override_pattern=""), "hybrid_override_pattern"),
    (dict(n_groups=3), "n_groups"),
    (dict(time_step_limit=[0, 10.0]), "time_step_limit"),
    (dict(time_step_limit=[0.001, None]), "time_step_limit"),
    (dict(mlp_hidden_act="silu"), "mlp_hidden_act"),
    (dict(mlp_hidden_act="gelu"), "mlp_hidden_act"),
    (dict(mamba_hidden_act="gelu"), "mamba_hidden_act"),
    (dict(layer_types=["conv", "full_attention"]), "layer_types"),
    (dict(first_k_dense_replace=1), "first_k_dense_replace"),
    (dict(num_hidden_layers=4), "num_hidden_layers"),
    (dict(ssm_state_size=None), "ssm_state_size"),
    (dict(hybrid_override_pattern="M-"), "intermediate_size"),
    (dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8), "kv_lora_rank"),
    (dict(n_group=2), "n_group"),
])
def test_specs_that_are_not_expressed_are_refused_by_name(change, names):
    """At ``create_model``, not at the first trace."""
    with pytest.raises(ValueError, match=names):
        build(dict(SPEC, **change))


def test_relu2_is_refused_in_a_stack_of_layer_types_and_unknown_keys_by_name():
    with pytest.raises(ValueError, match="mlp_hidden_act"):
        create_model("decoder", "random_tokens", (LENGTH,), VOCAB, num_experts=4,
                     mlp_hidden_act="relu2")
    # HF's mamba_proj_bias / attention_bias / mlp_bias true have no key here
    with pytest.raises(TypeError, match="mamba_proj_bias"):
        build(dict(SPEC, mamba_proj_bias=True))


def test_create_model_reports_one_site_no_rope_and_the_span_constants():
    model = build(SPEC)
    assert model.module.kinds() == tuple(PARTS[c] for c in "MEM*E")
    assert model.module.attention_sites() == ((4, 2, 8),) and model.module.rope_sites() == ()
    assert model.counters == COUNTERS + (BIAS_COUNTER,)
    assert model.flush_attrs(1) == {
        "attn_kernel_sites": 0, "attn_sites": 1, "moe_kernel_sites": 0, "moe_grouped_sites": 12,
        "moe_slot_kernel_sites": 0, "moe_slot_sites": 4, "ssd_kernel_sites": 0, "ssd_sites": 2,
        "hidden": 32, "expert_width": 12, "layers": 2, "expert_layers": 2, "top_k": 2,
        "expert_products": 2, "shared_width": 20,
        "ssm_layers": 2, "ssm_heads": 8, "ssm_head_dim": 8, "ssm_state": 16, "ssm_groups": 2,
        "ssm_chunk": 16, "embed_kernel_sites": 0, "embed_sites": 1}
    # the published shapes: one site of 32 query heads on 2 key/value heads of
    # 128, which takes the kernel at 4096 (16 query heads a key head)
    wide = build(dict(SPEC, num_attention_heads=32, head_dim=128, hybrid_override_pattern="M*"), 4096)
    assert wide.module.attention_sites() == ((32, 2, 128),) and takes_kernel(4096, 32, 2, 128)
    # the accepted specs carry three products a pair and no state-space constants
    mellum = create_model("decoder", "random_tokens", (LENGTH,), VOCAB, num_experts=4)
    assert mellum.flush_attrs(1)["expert_products"] == 3
    assert not any(k.startswith("ssm_") for k in mellum.flush_attrs(1))
    assert mellum.module.rope_sites()
    only_mixers = build(dict(SPEC, hybrid_override_pattern="MM"))
    assert only_mixers.module.attention_sites() == () and only_mixers.counters == ()
    assert set(only_mixers.flush_attrs(1)) == {
        "ssd_kernel_sites", "ssd_sites", "ssm_layers", "ssm_heads", "ssm_head_dim", "ssm_state",
        "ssm_groups", "ssm_chunk", "embed_kernel_sites", "embed_sites"}


def test_the_sites_are_what_the_traced_layers_hand_the_attention_core(monkeypatch):
    """The attention core is called once for each ``*`` layer and by no other
    part, with the shapes ``attention_sites`` reports, and q and k arrive as
    projected: no rotary call is traced."""
    import fedml_tpu.models.decoder as decoder

    seen, turned = [], []

    def spy(q, k, v, causal=False, window=None, q_rope=None, k_rope=None, scale=None):
        seen.append((q.shape[2], k.shape[2], q.shape[3]))
        assert causal and window is None and q_rope is None
        return jnp.zeros(q.shape[:3] + (v.shape[-1],), q.dtype)

    monkeypatch.setattr(decoder, "attention", spy)
    monkeypatch.setattr(decoder, "rotary", lambda *a: turned.append(a) or a[0])
    for pattern in ("MEM*E", "MM", "*M*E"):
        seen.clear()
        model = build(dict(SPEC, hybrid_override_pattern=pattern))
        jax.eval_shape(model.init, jax.random.PRNGKey(0))
        assert tuple(seen) == model.module.attention_sites() and len(seen) == pattern.count("*")
    assert not turned


def test_every_part_of_each_layer_kind_is_a_scope_directly_under_it():
    """A device trace splits a layer by these names (``tools/anatomy.py``
    reads the two path parts after the model): ``in_proj``, ``conv``, ``ssd``,
    ``gated_norm`` and ``out`` in an ``M`` layer, with the operator's
    ``ssd_chunk``, ``ssd_state`` and ``ssd_out`` beneath ``ssd``; ``qkv``,
    ``attention_full`` and ``out`` and NO ``rope`` in a ``*`` layer; the five
    of an expert layer; no method's own scope between the layer and them, in
    the forward or the backward pass."""
    model = build(SPEC)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def loss(v, x):
        return jnp.sum(model.apply(v, x, train=True)[0])

    text = jax.jit(jax.grad(loss)).lower(
        variables, jax.ShapeDtypeStruct((2, LENGTH), jnp.int32)).as_text(debug_info=True)
    under = {i: set(re.findall(rf"layers_{i}/([\w.]+)", text)) for i in range(5)}
    for i in (0, 2):
        assert {"in_proj", "conv", "ssd", "gated_norm", "out"} <= under[i], under[i]
        beneath = set(re.findall(rf"layers_{i}/ssd/(?:\w+/)*?(ssd_\w+)", text))
        assert beneath == {"ssd_chunk", "ssd_state", "ssd_out"}, beneath
    for i in (1, 4):
        assert {"router", "dispatch", "shared"} <= under[i], under[i]
    # the bounded rows' pass is one jitted function that every expert layer
    # calls (``_held_rows``, as in the accepted cells): its scopes stand at the
    # head of their own paths in the lowered text
    assert {"dispatch", "experts", "combine"} <= set(re.findall(r'"(\w+)/', text))
    assert {"qkv", "attention_full", "out"} <= under[3]
    assert not any("rope" in names or "qk_norm" in names for names in under.values())
    assert not {"qkv", "attention_full", "router"} & (under[0] | under[2])
    assert not {"in_proj", "ssd", "conv"} & (under[1] | under[3] | under[4])
    assert not any("." in name for names in under.values() for name in names)
    # and no rotary table is built anywhere in the program
    assert "cos" not in re.findall(r"\b(cos|sin)\b", text)


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
        for i in (1, 4):
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
    """The one-part stack through ``FedAvgAPI.train()`` under both client
    schedules: the same parameters (float32, the order of sums aside: the
    latent decoder's 2e-6; the state-space leaves ``A_log``, ``D`` and
    ``dt_bias`` are averaged as they are, an average of logarithms for
    ``A_log``), the state-space constants, the experts' two products and one
    attention site on the ``flush`` span and no ``rope_sites``, the expert
    counters summed over the two expert layers, and a selection bias that
    local training leaves as it came and the average returns to within its
    own rounding."""
    rows_v, flushes_v, params_v, bias0, bias_v = one_round("vmap")
    rows_s, flushes_s, params_s, _, bias_s = one_round("scan")
    assert bias0.any()
    assert np.allclose(bias0, bias_v, rtol=3e-7, atol=0) and np.allclose(bias0, bias_s, rtol=3e-7, atol=0)
    for name in params_v:
        assert jnp.allclose(params_v[name], params_s[name], rtol=0, atol=2e-6), name
    for flushes in (flushes_v, flushes_s):
        assert flushes and all(a["moe_dropped"] == 0 for a in flushes)
        a = flushes[0]
        assert [a[k] for k in ("ssm_layers", "ssm_heads", "ssm_head_dim", "ssm_state",
                               "ssm_groups", "ssm_chunk")] == [2, 8, 8, 16, 2, 16]
        assert (a["attn_sites"], a["attn_kernel_sites"]) == (1, 0)
        assert "rope_sites" not in a and "rope_kernel_sites" not in a and "conv_layers" not in a
        # two scans a step, at widths and chunks the scan's kernels do not take
        assert (a["ssd_sites"], a["ssd_kernel_sites"]) == (2, 0)
        assert a["layers"] == a["expert_layers"] == 2 and a["expert_products"] == 2
        # up and down, each with its two gradients, under a lane tile's width
        assert (a["moe_grouped_sites"], a["moe_kernel_sites"]) == (12, 0)
        assert a["shared_width"] == 20
        # 2 rounds x 3 clients x 2 steps x 2 expert layers, top-2 of 2 x 32 tokens
        assert sum(a["moe_calls"] for a in flushes) == 24
        assert 0 < sum(a["moe_bias_moved"] for a in flushes) < 24 * 64 * 2
    assert sum(a["moe_bias_moved"] for a in flushes_v) == sum(
        a["moe_bias_moved"] for a in flushes_s)
    losses = [r["Train/Loss"] for r in rows_v if "Train/Loss" in r]
    assert len(losses) == 2 and losses[1] < losses[0]
