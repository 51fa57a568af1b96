"""The decoder's fifth block shape (Laguna-XS.2, HF's ``laguna``): query
heads that differ by layer on one set of key/value heads, rotary on a part
of each head in one layer kind and on all of it in the other, a window
shorter than the sequence, a sigmoid router without a selection bias over
a held share of the experts with a shared expert beside them, a leading
dense layer. Against the equations of its plain reference
(benchmarks/configs/laguna-xs.2_ref.py), at small sizes on the CPU in float32
with seeded weights; the partial rotary tables against HF's YaRN written
out; the partial rotary operator against its plain form; the shares of the
expert-parallel deployment against the uncut layer; the sites and span
constants the program reports; and the keys refused and accepted by name."""

import copy
import importlib.util
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import create_model
from fedml_tpu.models.decoder import rotary_tables, routed_experts
from fedml_tpu.ops import grouped_matmul, rotary as op
from fedml_tpu.ops.attention import takes_kernel

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from benchmarks.lib import fedavg_ref  # noqa: E402

CONFIG = json.loads((ROOT / "benchmarks" / "configs" / "laguna-xs.2.json").read_text())
PUBLISHED_ROPE = CONFIG["published"]["rope_parameters"]
VOCAB = 61
# Every mechanism of the published spec, small: a dense full layer and one
# period (3 sliding, 1 full) with experts; 6 | 8 query heads on ONE key/value
# head of 16 (the published 48 | 64 on 8: 6 | 8 a key/value head); the full
# layers turn 8 of 16 dims by YaRN, the sliding ones all 16 by the default
# type; a window of 8 under 24 positions; top-4 of 16 sigmoid-scored experts,
# 4 of them held, one shared expert; the router's scale 2.5.
SPEC = dict(
    hidden_size=32, intermediate_size=48, head_dim=16, num_attention_heads=6,
    num_key_value_heads=1, num_attention_heads_per_layer=[6, 8, 8, 8, 6],
    layer_types=["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"],
    sliding_window=8,
    rope_parameters={"full_attention": dict(PUBLISHED_ROPE["full_attention"],
                                            original_max_position_embeddings=16),
                     "sliding_attention": PUBLISHED_ROPE["sliding_attention"],
                     "original_max_position_embeddings": 16},
    first_k_dense_replace=1, num_experts=16, num_experts_per_tok=4, moe_intermediate_size=12,
    n_shared_experts=1, moe_shared_expert_intermediate_size=12, scoring_func="sigmoid",
    norm_topk_prob=True, routed_scaling_factor=2.5, rms_norm_eps=1e-6,
    tie_word_embeddings=False, experts_held=[4, 8],
)
LENGTH = 24
# The kernels' route: heads of 128 (the published width; 64 of them turned on
# the full layers) at the shortest length the kernels take, a window of 128.
KERNEL_SPEC = dict(SPEC, hidden_size=64, head_dim=128, num_attention_heads_per_layer=[6, 8, 6],
                   layer_types=["full_attention", "sliding_attention", "full_attention"],
                   sliding_window=128)
KERNEL_LENGTH = 256


def reference():
    path = ROOT / "benchmarks" / "configs" / "laguna-xs.2_ref.py"
    spec = importlib.util.spec_from_file_location("laguna_ref", path)
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


def both_sides(spec, length, docs, dtype=jnp.float32):
    """(program, reference) as (logits, loss, gradients) on the reference's
    seed weights; the program's held in ``dtype``."""
    ref, cfg = reference(), config(spec, length)
    model = create_model("decoder", "random_tokens", (length,), VOCAB, **cfg["model"]["kwargs"])
    flat = ref.init_params(5, cfg)
    have = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert {k: v.shape for k, v in flatten(have["params"]).items()} == ref.param_shapes(cfg)
    doc = jax.random.randint(jax.random.PRNGKey(9), (docs, length + 1), 1, VOCAB)
    x, y = doc[:, :-1], doc[:, 1:]
    mask = jnp.ones((docs,), jnp.float32)

    def program(flat):
        logits, _ = model.apply({"params": nest({k: v.astype(dtype) for k, v in flat.items()})},
                                x, train=True)
        return fedavg_ref.task_loss("nwp", logits, y, mask)[0], logits.astype(jnp.float32)

    def plain(flat):
        logits = ref.logits_fn(flat, x, fedavg_ref.REFERENCE, cfg)
        return fedavg_ref.task_loss("nwp", logits, y, mask)[0], logits

    out = []
    for fn in (program, plain):
        (loss, logits), grads = jax.jit(jax.value_and_grad(fn, has_aux=True))(flat)
        out.append((logits, float(loss), grads))
    return model, out


def gaps(program, plain):
    """(logits' largest gap over their largest size, the loss's relative gap,
    the worst leaf's largest gradient gap over its largest gradient)."""
    (lp, fp, gp), (lr, fr, gr) = program, plain
    logits = float(jnp.max(jnp.abs(lp - lr))) / float(jnp.max(jnp.abs(lr)))
    worst = 0.0
    for name in gr:
        scale = float(jnp.max(jnp.abs(gr[name])))
        assert scale > 0, name
        worst = max(worst, float(jnp.max(jnp.abs(gp[name] - gr[name]))) / scale)
    return logits, abs(fp - fr) / abs(fr), worst


ROUTES = {
    # spec, length, documents, whether attention and rotary take the kernels
    "plain_route": (SPEC, LENGTH, 3, False),
    "kernel_route_interpreted": (KERNEL_SPEC, KERNEL_LENGTH, 1, True),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_logits_loss_and_every_gradient_match_the_reference(route):
    """The whole model, forward and gradient. Both sides are exact float32
    on the CPU and differ by the order of their sums (the reference writes
    the scores in blocks against the live keys and turns q and k in its own
    three lines): 1e-5 of the largest logit, 1e-6 of the loss, 2e-5 of a
    leaf's largest gradient."""
    spec, length, docs, kernel = ROUTES[route]
    model, (program, plain) = both_sides(spec, length, docs)
    assert all(takes_kernel(length, *site) is kernel for site in model.module.attention_sites())
    assert all(op.takes_kernel(length, *site) is kernel for site in model.module.rope_sites())
    logits, loss, grads = gaps(program, plain)
    assert logits <= 1e-5 and loss <= 1e-6 and grads <= 2e-5, (logits, loss, grads)


def test_the_tolerances_catch_a_program_computed_in_bfloat16():
    """The same comparison with the program's weights and activations in
    bfloat16, where the configuration states float32 for this comparison:
    every one of the three gaps is over its limit, the logits' and the
    gradients' by orders (4.6e-3 and 0.61 when written), the loss's by 2.5 x
    (its mean over 72 positions averages the rounding away)."""
    _, (program, plain) = both_sides(SPEC, LENGTH, 3, dtype=jnp.bfloat16)
    logits, loss, grads = gaps(program, plain)
    assert logits > 1e-4 and loss > 1e-6 and grads > 1e-2, (logits, loss, grads)


def hf_yarn(rope, head_dim, length):
    """HF's ``_compute_yarn_parameters`` and ``apply_rotary_pos_emb`` tables,
    written out in float64: ``dim = int(head_dim * partial_rotary_factor)``,
    the correction range over ``dim``, cos and sin times
    ``attention_factor``."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    base, factor = rope["rope_theta"], rope["factor"]
    original = rope["original_max_position_embeddings"]
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    inv_freq = interpolation * (1 - extrapolation_factor) + extrapolation * extrapolation_factor
    freqs = np.outer(np.arange(length, dtype=np.float64), inv_freq)
    emb = np.concatenate([freqs, freqs], axis=-1)
    return np.cos(emb) * rope["attention_factor"], np.sin(emb) * rope["attention_factor"]


def test_partial_yarn_tables_are_hf_s_over_the_turned_dims():
    """The full layers' tables at the published numbers: 64 of 128 dims, the
    correction range over those 64 (HF's ``dim``), ``attention_factor`` on
    the turned dims only; the pass-through dims have no table at all, and the
    operator leaves them as they came. float32 angles against float64: 4e-4
    at position 4 095 (an angle of 4 095 x 1 in float32 is good to 2.4e-4,
    times attention_factor 1.416: 3.4e-4)."""
    rope, T = PUBLISHED_ROPE["full_attention"], 4096
    cos, sin = rotary_tables(rope, 128, T)
    want_cos, want_sin = hf_yarn(rope, 128, T)
    assert cos.shape == sin.shape == (T, 64)
    assert np.max(np.abs(np.asarray(cos) - want_cos)) <= 4e-4
    assert np.max(np.abs(np.asarray(sin) - want_sin)) <= 4e-4
    assert np.allclose(np.asarray(cos[0]), rope["attention_factor"], rtol=1e-7)
    # over all 128 dims HF's ramp would differ: the width matters
    assert rotary_tables(dict(rope, partial_rotary_factor=1.0), 128, T)[0].shape == (T, 128)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, T, 2, 128))
    turned = op.rotary(x, cos, sin)
    assert np.array_equal(np.asarray(turned[..., 64:]), np.asarray(x[..., 64:]))
    # the sliding layers: the default type over every dim at theta 1e4, unscaled
    cos, sin = rotary_tables(PUBLISHED_ROPE["sliding_attention"], 128, 8)
    assert cos.shape == (8, 128) and float(cos[0, 0]) == 1.0
    inv = 1e4 ** (-np.arange(0, 128, 2) / 128)
    assert np.allclose(np.asarray(sin[5, :64]), np.sin(5 * inv), atol=1e-6)


def plain_partial(x, cos, sin):
    """HF's ``cat(x_rot * cos + rotate_half(x_rot) * sin, x_pass)`` on x [B,
    T, H, D], products and sum in float32, back in x's dtype."""
    R = cos.shape[-1]
    rot = x[..., :R].astype(jnp.float32)
    half = jnp.concatenate([-rot[..., R // 2:], rot[..., :R // 2]], axis=-1)
    out = rot * cos[None, :, None, :] + half * sin[None, :, None, :]
    return jnp.concatenate([out.astype(x.dtype), x[..., R:]], axis=-1)


@pytest.mark.parametrize("shape,turned", [
    ((2, 256, 3, 128), 64),     # the full layers' q at a head a lane tile
    ((1, 256, 4, 64), 32),      # two heads a tile, half of each turned
    ((1, 256, 2, 128), 128),    # the whole head, as before
    ((2, 24, 3, 16), 8),        # the plain form's shapes
])
def test_partial_rotary_operator_is_the_plain_form(shape, turned):
    """Value and gradient through the kernel (interpreted) where the shapes
    take it, to the last bit of bfloat16 on tables and inputs rounded
    through bfloat16 (``tests/test_rotary.py`` has why: the CPU contracts
    the plain form's sum, the kernel's is not, and exact products make the
    two the same)."""
    B, T, H, D = shape
    rope = dict(PUBLISHED_ROPE["full_attention"], partial_rotary_factor=turned / D)
    cos, sin = (a.astype(jnp.bfloat16).astype(jnp.float32) for a in rotary_tables(rope, D, T))
    x, g = (jax.random.normal(jax.random.PRNGKey(i), shape, jnp.bfloat16) for i in (0, 1))
    assert op.takes_kernel(T, H, D, turned) is (T % 256 == 0)
    ours, pull = jax.vjp(lambda x: op.rotary(x, cos, sin), x)
    want, pull_want = jax.vjp(lambda x: plain_partial(x, cos, sin), x)
    for a, b in ((ours, want), (pull(g)[0], pull_want(g)[0])):
        assert np.array_equal(np.asarray(a).view(np.uint16), np.asarray(b).view(np.uint16))
    # the backward of a turn is the turn back: the passing dims' gradient is g's
    assert np.array_equal(np.asarray(pull(g)[0][..., turned:]), np.asarray(g[..., turned:]))


def test_the_sixteen_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """model-configs guide, section 4, at the published router: 256 experts
    over 16 chips, 16 held each, top-8 sigmoid scores renormalised and
    scaled by 2.5, no bias. Every chip computes the shared expert alike, so
    it is counted once; the 16 routed parts and it add up to the layer with
    every expert held. 1e-5: the order of sixteen partial sums in float32."""
    d, f, E, N = 32, 8, 256, 40
    keys = jax.random.split(jax.random.PRNGKey(2), 8)
    n = jax.random.normal(keys[0], (N, d))
    router = 0.2 * jax.random.normal(keys[1], (d, E))
    gate, up = (0.2 * jax.random.normal(k, (E, d, f)) for k in keys[2:4])
    down = 0.2 * jax.random.normal(keys[4], (E, f, d))
    sg, su = (0.2 * jax.random.normal(k, (d, f)) for k in keys[5:7])
    sd = 0.2 * jax.random.normal(keys[7], (f, d))
    shared = (jax.nn.silu(n @ sg) * (n @ su)) @ sd
    rules = dict(top_k=8, scoring="sigmoid", scale=2.5, renorm_eps=1e-20)
    uncut, counted = routed_experts(n, router, gate, up, down, **rules)
    parts, pairs = jnp.zeros_like(uncut), 0.0
    for chip in range(16):
        lo = 16 * chip
        y, c = routed_experts(n, router, *(w[lo:lo + 16] for w in (gate, up, down)),
                              held_from=lo, **rules)
        parts, pairs = parts + y, pairs + float(c[0])
    assert pairs == float(counted[0]) == N * 8
    total, want = parts + shared, uncut + shared
    assert float(jnp.max(jnp.abs(total - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))


def test_the_cell_s_sites_constants_and_parameters_are_the_published_ones():
    """At the cell's size: one attention site a layer with its own heads
    (48 | 64 on 8 of 128), two rotary calls a layer (64 of 128 dims turned
    on the full layers), every one of them the kernels' at T = 4 096, the
    grouped products at K 2 048 / N 512 the kernels' too, and the token
    table's gradient; the ``flush`` span's per-kind constants; 489 707 520
    held parameters."""
    m = CONFIG["model"]
    model = create_model("decoder", m["dataset"], tuple(m["input_shape"]), m["num_classes"],
                         **m["kwargs"])
    full, sliding = (48, 8, 128), (64, 8, 128)
    assert model.module.attention_sites() == (full, sliding, sliding, sliding, full)
    assert model.module.rope_sites() == ((48, 128, 64), (8, 128, 64)) + (
        (64, 128), (8, 128)) * 3 + ((48, 128, 64), (8, 128, 64))
    assert all(takes_kernel(4096, *site) for site in model.module.attention_sites())
    assert all(op.takes_kernel(4096, *site) for site in model.module.rope_sites())
    sites = model.module.grouped_sites(4096)
    assert set(sites) == {(4096, 2048, 512, 16), (4096, 512, 2048, 16)} and len(sites) == 12
    assert all(grouped_matmul.takes_kernel(*site) for site in sites)
    assert model.flush_attrs(1) == {
        "attn_kernel_sites": 5, "attn_sites": 5, "rope_kernel_sites": 10, "rope_sites": 10,
        "moe_kernel_sites": 36, "moe_grouped_sites": 36, "moe_slot_kernel_sites": 8,
        "moe_slot_sites": 8, "hidden": 2048, "expert_width": 512, "layers": 4,
        "expert_layers": 4, "top_k": 8, "expert_products": 3, "shared_width": 512,
        "attn_length": 4096, "attn_window": 512,
        "attn_full_layers": 2, "attn_full_heads": 48, "attn_full_kv_heads": 8,
        "attn_full_head_dim": 128, "attn_full_rotary_dim": 64, "attn_sliding_layers": 3,
        "attn_sliding_heads": 64, "attn_sliding_kv_heads": 8, "attn_sliding_head_dim": 128,
        "attn_sliding_rotary_dim": 128, "embed_kernel_sites": 1, "embed_sites": 1}
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) == 489_707_520
    # one head count and no window between the kinds: no per-kind constants
    for other in ("mellum2-12b-a2.5b", "lfm2-8b-a1b", "kanana-2-30b-a3b"):
        o = json.loads((ROOT / "benchmarks" / "configs" / f"{other}.json").read_text())["model"]
        built = create_model("decoder", o["dataset"], tuple(o["input_shape"]), o["num_classes"],
                             **o["kwargs"])
        attrs = built.flush_attrs(1)
        assert "attn_window" not in attrs and not any(
            k.startswith(("attn_full_", "attn_sliding_")) for k in attrs), other


def test_the_sites_are_what_the_traced_layers_hand_the_operators(monkeypatch):
    import fedml_tpu.models.decoder as decoder

    attention_seen, rope_seen = [], []

    def attention_spy(q, k, v, causal=False, window=None, **_):
        attention_seen.append((q.shape[2], k.shape[2], q.shape[3]))
        return jnp.zeros_like(q)

    def rope_spy(x, cos, sin):
        R = cos.shape[-1]
        rope_seen.append(x.shape[2:] + ((R,) if R < x.shape[-1] else ()))
        return x

    monkeypatch.setattr(decoder, "attention", attention_spy)
    monkeypatch.setattr(decoder, "rotary", rope_spy)
    model = create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **SPEC)
    jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert tuple(attention_seen) == model.module.attention_sites() == (
        (6, 1, 16), (8, 1, 16), (8, 1, 16), (8, 1, 16), (6, 1, 16))
    assert tuple(rope_seen) == model.module.rope_sites()
    assert model.module.rope_sites()[:2] == ((6, 16, 8), (1, 16, 8))


def test_the_configuration_file_builds_and_keeps_the_source_s_keys():
    """model.kwargs is the translation; the top level holds every key of the
    source's config.json, the reduced ones changed and listed."""
    published = CONFIG["published"]
    changed = {k for k in published if CONFIG[k] != published[k]}
    assert changed == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert changed <= set(CONFIG["reduced"])
    kw = CONFIG["model"]["kwargs"]
    L = len(kw["layer_types"])
    assert kw["layer_types"] == published["layer_types"][:L]
    assert kw["num_attention_heads_per_layer"] == published["num_attention_heads_per_layer"][:L]
    assert [t == "dense" for t in published["mlp_layer_types"][:L]] == [
        i < kw["first_k_dense_replace"] for i in range(L)]
    assert kw["routed_scaling_factor"] == published["moe_routed_scaling_factor"]
    assert kw["moe_shared_expert_intermediate_size"] == published["shared_expert_intermediate_size"]
    assert kw["rope_parameters"] == published["rope_parameters"]


@pytest.mark.parametrize("change,error,name", [
    # the source's spellings that model.kwargs translates, and keys it drops
    (dict(partial_rotary_factor=0.5), TypeError, "partial_rotary_factor"),
    (dict(mlp_layer_types=["dense"] + ["sparse"] * 4), TypeError, "mlp_layer_types"),
    (dict(moe_routed_scaling_factor=2.5), TypeError, "moe_routed_scaling_factor"),
    (dict(shared_expert_intermediate_size=12), TypeError, "shared_expert_intermediate_size"),
    (dict(gating=True), TypeError, "gating"),
    (dict(attention_bias=False), TypeError, "attention_bias"),
    # what rope_parameters may hold
    (dict(rope_parameters={**SPEC["rope_parameters"], "mrope_section": [8, 8]}),
     ValueError, "mrope_section"),
    (dict(rope_parameters={**SPEC["rope_parameters"], "full_attention": dict(
        SPEC["rope_parameters"]["full_attention"], mscale=1.0)}), ValueError, "mscale"),
    (dict(rope_parameters={**SPEC["rope_parameters"], "full_attention": dict(
        SPEC["rope_parameters"]["full_attention"], partial_rotary_factor=0.3125)}),
     ValueError, "partial_rotary_factor"),
    # head counts a layer
    (dict(num_attention_heads_per_layer=[6, 8, 8, 8]), ValueError, "num_attention_heads_per_layer"),
    (dict(num_key_value_heads=2, num_attention_heads=8,
          num_attention_heads_per_layer=[6, 8, 8, 8, 5]), ValueError, "num_attention_heads_per_layer"),
])
def test_keys_that_are_not_expressed_are_refused_by_name(change, error, name):
    with pytest.raises(error, match=name):
        create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **dict(SPEC, **change))


def test_per_layer_heads_and_partial_rotary_beside_a_latent_are_refused_by_name():
    latent = dict(hidden_size=32, num_attention_heads=2, num_hidden_layers=2, kv_lora_rank=24,
                  q_lora_rank=None, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **dict(
            latent, rope_parameters={"full_attention": {"rope_theta": 1e6,
                                                        "partial_rotary_factor": 0.5}}))
    with pytest.raises(ValueError, match="num_attention_heads_per_layer"):
        create_model("decoder", "random_tokens", (LENGTH,), VOCAB,
                     **dict(latent, num_attention_heads_per_layer=[2, 2]))
    # a factor of 1 says nothing a latent model does not do already
    create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **dict(
        latent, rope_parameters={"full_attention": {"rope_theta": 1e6, "partial_rotary_factor": 1}}))
