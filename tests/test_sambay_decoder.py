"""The decoder's sixth block shape (Phi-4-mini-flash-reasoning, HF's
``phi4flash``: SambaY, arXiv:2507.06607): a Mamba-1 mixer whose scan output
is handed on as a memory, differential attention on a window, on all keys
and across to the keys and values that an earlier layer kept, a Gated Memory
Unit over the memory, LayerNorms with a bias and a tied head. Against the
equations of its plain reference
(benchmarks/configs/phi-4-mini-flash-reasoning_ref.py), at small sizes on the
CPU in float32 with seeded weights, by the plain forms and by both kernels
interpreted; that the gradients of the layers that keep state sum what the
later layers read; the layers' kinds from the published indices; the sites,
scopes and span constants the program reports; and the keys refused by
name."""

import copy
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
from fedml_tpu.ops import selective_scan as sscan, table_grad
from fedml_tpu.ops.attention import takes_kernel

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
from benchmarks.lib import fedavg_ref  # noqa: E402

CONFIG = json.loads(
    (ROOT / "benchmarks" / "configs" / "phi-4-mini-flash-reasoning.json").read_text())
VOCAB = 61
# Every mechanism of the published stack, small: published layers 3..7 of 8
# (a window layer, the Mamba-1 layer that keeps the memory, the full layer
# that keeps its keys and values, a GMU, a cross layer), 4 query heads on 2
# key/value heads of 16 (pairs of 2 on 1), a window of 8 under 24 positions,
# a state of 16 over 128 channels.
SPEC = dict(
    hidden_size=64, intermediate_size=96, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_hidden_layers=8, mb_per_layer=2, layers_held=[3, 8], sliding_window=8,
    layer_norm_eps=1e-5, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
    tie_word_embeddings=True,
)
LENGTH = 24
# The kernels' route: heads of 64 (the published width; values of 128) at the
# shortest length the attention kernel takes, 512 channels of the scan (one
# block of the forward kernel, two of the backward), a window of 128.
KERNEL_SPEC = dict(SPEC, hidden_size=256, head_dim=64, sliding_window=128)
KERNEL_LENGTH = 256


def reference():
    path = ROOT / "benchmarks" / "configs" / "phi-4-mini-flash-reasoning_ref.py"
    spec = importlib.util.spec_from_file_location("phi4flash_ref", path)
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


def both_sides(spec, length, docs, dtype=jnp.float32, ref=None, repeated=False):
    """(program, reference) as (logits, loss, gradients) on the reference's
    seed weights; the program's held in ``dtype``. ``repeated``: documents of
    the pad id 0 at every third position and four other ids between, so that
    each row of the tied table's gradient sums many rows."""
    ref, cfg = ref or reference(), config(spec, length)
    model = create_model("decoder", "random_tokens", (length,), VOCAB, **cfg["model"]["kwargs"])
    flat = ref.init_params(5, cfg)
    have = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert {k: v.shape for k, v in flatten(have["params"]).items()} == ref.param_shapes(cfg)
    doc = jax.random.randint(jax.random.PRNGKey(9), (docs, length + 1), 1, VOCAB)
    if repeated:
        doc = jnp.where(jnp.arange(length + 1) % 3 == 0, 0, doc % 4 + 1)
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
    the worst leaf's largest gradient gap over its largest gradient, the same
    over the four lambda leaves of each differential layer)."""
    (lp, fp, gp), (lr, fr, gr) = program, plain
    logits = float(jnp.max(jnp.abs(lp - lr))) / float(jnp.max(jnp.abs(lr)))
    worst = {False: 0.0, True: 0.0}
    for name in gr:
        scale = float(jnp.max(jnp.abs(gr[name])))
        assert scale > 0, name
        lam = "/lambda_" in name
        worst[lam] = max(worst[lam], float(jnp.max(jnp.abs(gp[name] - gr[name]))) / scale)
    return logits, abs(fp - fr) / abs(fr), worst[False], worst[True]


ROUTES = {
    # spec, length, documents, whether attention, the scan and the tied
    # table's gradient take the kernels, whether the ids repeat
    "plain_route": (SPEC, LENGTH, 3, False, False),
    "kernel_route_interpreted": (KERNEL_SPEC, KERNEL_LENGTH, 1, True, False),
    "kernel_route_repeated_ids": (KERNEL_SPEC, KERNEL_LENGTH, 1, True, True),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_logits_loss_and_every_gradient_match_the_reference(route):
    """The whole stack, forward and gradient. Both sides are exact float32
    on the CPU and differ by the order of their sums (the reference writes
    the scores in blocks against the live keys, both maps apart, and scans
    the positions in blocks; the program's kernels pad the keys to the
    values' width and walk the scan in groups of positions): 1e-5 of the
    largest logit, 1e-6 of the loss, 2e-5 of a leaf's largest gradient, and
    2e-4 for the lambda leaves, whose gradient is ONE sum over every
    position, pair and lane of ``dL/do * map2`` that cancels to a small share
    of its terms' size (8.2e-5 on the kernel route when written: the order
    of the sums shows through the cancellation). With repeated ids (the pad
    id 0 among them) the table's gradient adds up to 86 rows into one."""
    spec, length, docs, kernel, repeated = ROUTES[route]
    model, (program, plain) = both_sides(spec, length, docs, repeated=repeated)
    m = model.module.mamba1_spec()
    assert all(takes_kernel(length, *site) is kernel for site in model.module.attention_sites())
    assert sscan.takes_kernel(length, m.inner, m.state) is kernel
    assert table_grad.takes_kernel(VOCAB, spec["hidden_size"], docs * length) is kernel
    logits, loss, grads, lam = gaps(program, plain)
    assert logits <= 1e-5 and loss <= 1e-6 and grads <= 2e-5 and lam <= 2e-4, (
        logits, loss, grads, lam)


def test_the_tolerances_catch_a_program_computed_in_bfloat16():
    """The same comparison with the program's weights and activations in
    bfloat16, where the comparison states float32: the logits' and the
    gradients' gaps are over their limits by orders."""
    _, (program, plain) = both_sides(SPEC, LENGTH, 3, dtype=jnp.bfloat16)
    logits, loss, grads, lam = gaps(program, plain)
    assert logits > 1e-4 and grads > 1e-2 and lam > 2e-4, (logits, loss, grads, lam)


def test_the_layers_that_keep_state_sum_the_gradients_of_those_that_read_it():
    """Cut what is handed on (the memory to the GMU, the keys and values to
    the cross layer) from the reference's gradient by a ``stop_gradient``:
    the gradients of the keeping layers' own leaves change, and the
    program's stay those of the uncut reference. So the cross layer's and
    the GMU's contributions reach layer 17's ``Wqkv`` and layer 16's scan."""
    ref = reference()
    _, (program, plain) = both_sides(SPEC, LENGTH, 3, ref=ref)
    mamba, differential = ref._mamba, ref._differential

    def cut_mamba(*a):
        out, y = mamba(*a)
        return out, jax.lax.stop_gradient(y)

    def cut_differential(*a):
        out, kv = differential(*a)
        return out, jax.lax.stop_gradient(kv)

    ref._mamba, ref._differential = cut_mamba, cut_differential
    _, (_, cut) = both_sides(SPEC, LENGTH, 3, ref=ref)
    # layers_1 is the Mamba-1 layer that keeps the memory, layers_2 the full
    # layer that keeps its keys and values
    for leaf in ("layers_1/x_proj", "layers_1/dt_proj", "layers_1/A_log", "layers_1/in_proj",
                 "layers_2/Wqkv"):
        scale = float(jnp.max(jnp.abs(plain[2][leaf])))
        assert float(jnp.max(jnp.abs(cut[2][leaf] - plain[2][leaf]))) > 1e-2 * scale, leaf
        assert float(jnp.max(jnp.abs(program[2][leaf] - plain[2][leaf]))) <= 2e-5 * scale, leaf
    # what no later layer reads is not handed on: layers_0's leaves do not move
    for leaf in ("layers_0/Wqkv", "layers_0/out_proj"):
        scale = float(jnp.max(jnp.abs(plain[2][leaf])))
        assert float(jnp.max(jnp.abs(cut[2][leaf] - plain[2][leaf]))) < 0.5 * scale, leaf


@pytest.mark.parametrize("held,kinds", [
    ((15, 20), ("sliding_attention", "mamba1", "full_attention", "gmu", "cross_attention")),
    ((12, 16), ("mamba1", "sliding_attention", "mamba1", "sliding_attention")),
    ((16, 18), ("mamba1", "full_attention")),
    ((0, 32), ("mamba1", "sliding_attention") * 8 + ("mamba1", "full_attention")
     + ("gmu", "cross_attention") * 7),
])
def test_the_kinds_follow_the_published_index(held, kinds):
    """Mamba where l % 2 == 0, a window before 16, the memory at 16, the kept
    keys at 17, GMUs and cross layers from 18 on; at 16 and 17 they keep."""
    model = create_model("decoder", "random_tokens", (64,), VOCAB,
                         **dict(SPEC, num_hidden_layers=32, layers_held=list(held)))
    layers = model.module.sambay_layers()
    assert tuple(kind for _, kind, _ in layers) == kinds
    assert {l for l, _, keeps in layers if keeps} == {16, 17} & set(range(*held))


@pytest.mark.parametrize("held,source", [((18, 20), "mamba1"), ((17, 20), "mamba1"),
                                         ((16, 17), None), ((18, 19), "mamba1")])
def test_a_reader_without_what_it_reads_is_refused(held, source):
    kw = dict(SPEC, num_hidden_layers=32, layers_held=list(held))
    if source is None:
        create_model("decoder", "random_tokens", (64,), VOCAB, **kw)
        return
    with pytest.raises(ValueError, match=source):
        create_model("decoder", "random_tokens", (64,), VOCAB, **kw)


@pytest.mark.parametrize("change,name", [
    ({"layer_types": ["full_attention"] * 5}, "layer_types"),
    ({"hybrid_override_pattern": "M*M*M"}, "hybrid_override_pattern"),
    ({"tie_word_embeddings": False}, "tied head"),
    ({"num_attention_heads": 6, "num_key_value_heads": 3}, "halves"),
    ({"layers_held": [3, 9]}, "layers_held"),
])
def test_specs_that_are_not_expressed_are_refused_by_name(change, name):
    with pytest.raises(ValueError, match=name):
        create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **dict(SPEC, **change))


def test_the_cell_s_sites_constants_and_parameters_are_the_published_ones():
    """At the cell's size: both maps of the three attention layers on the
    kernel (20 query heads on 10 key/value heads of 64, values of 128), the
    scan on its kernels, the tied table's gradient on its kernel, and
    577 178 752 held parameters."""
    m = CONFIG["model"]
    model = create_model(m["name"], m["dataset"], tuple(m["input_shape"]), int(m["num_classes"]),
                         **m["kwargs"])
    assert model.module.attention_sites() == ((20, 10, 64, 0, 128),) * 6
    assert model.flush_attrs(1) == {
        "attn_kernel_sites": 6, "attn_sites": 6, "sscan_kernel_sites": 1, "sscan_sites": 1,
        "sscan_layers": 1, "sscan_inner": 5120, "sscan_state": 16, "diff_length": 4096,
        "diff_window": 512, "diff_sliding_layers": 1, "diff_full_layers": 1,
        "diff_cross_layers": 1, "diff_pairs": 20, "diff_kv_groups": 10, "diff_key_dim": 64,
        "diff_value_dim": 128, "embed_kernel_sites": 1, "embed_sites": 1}
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))["params"]
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) == 577_178_752
    # and at 64 tokens, the evaluation's, every core takes its plain form
    short = create_model(m["name"], m["dataset"], (64,), int(m["num_classes"]), **m["kwargs"])
    attrs = short.flush_attrs(256)
    assert attrs["attn_kernel_sites"] == attrs["sscan_kernel_sites"] == 0
    assert attrs["embed_kernel_sites"] == 0


def test_the_sites_are_what_the_traced_layers_hand_the_attention_core(monkeypatch):
    import fedml_tpu.models.decoder as decoder

    seen = []

    def spy(q, k, v, **kw):
        seen.append((q.shape[2], k.shape[2], q.shape[3], 0, v.shape[3]))
        return jnp.zeros(q.shape[:3] + (v.shape[-1],), q.dtype)

    monkeypatch.setattr(decoder, "attention", spy)
    model = create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **SPEC)
    jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert tuple(seen) == model.module.attention_sites() == ((2, 1, 16, 0, 32),) * 6


def test_every_part_of_each_layer_kind_is_a_scope_directly_under_it():
    """A device trace splits a layer by these names (``tools/anatomy.py``
    reads the two path parts after the model), in the forward or the
    backward pass, with no method's own scope between the layer and them."""
    model = create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **SPEC)
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def loss(v, x):
        return jnp.sum(model.apply(v, x, train=True)[0])

    text = jax.jit(jax.grad(loss)).lower(
        variables, jax.ShapeDtypeStruct((2, LENGTH), jnp.int32)).as_text(debug_info=True)
    want = {
        0: {"qkv", "attention_sliding", "diff_combine", "out", "mlp"},
        1: {"in_proj", "conv", "x_proj", "selective_scan", "gate", "out", "mlp"},
        2: {"qkv", "attention_full", "diff_combine", "out", "mlp"},
        3: {"gmu", "out", "mlp"},
        4: {"q", "attention_cross", "diff_combine", "out", "mlp"},
    }
    for i, scopes in want.items():
        under = set(re.findall(rf"layers_{i}/([\w.]+)", text))
        assert scopes <= under, (i, scopes - under)


def one_round(mode):
    from fedml_tpu.algorithms import FedAvgAPI
    from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
    from fedml_tpu.data.base import FederatedDataset
    from fedml_tpu.telemetry import get_tracer

    per_client, clients = 2, 2
    docs = np.random.default_rng(0).integers(
        1, VOCAB, size=(clients, per_client, LENGTH + 1), dtype=np.int32)
    data = FederatedDataset(
        name="random_tokens", client_x=list(docs[:, :, :-1]), client_y=list(docs[:, :, 1:]),
        test_x=docs[0, :2, :-1], test_y=docs[0, :2, 1:], num_classes=VOCAB)
    model = create_model("decoder", "random_tokens", (LENGTH,), VOCAB, **SPEC)
    cfg = RunConfig(
        data=DataConfig(batch_size=1, pad_bucket=1),
        fed=FedConfig(client_num_in_total=clients, client_num_per_round=clients, comm_round=2,
                      epochs=1, frequency_of_the_test=1, client_parallelism=mode),
        train=TrainConfig(client_optimizer="sgd", lr=0.05), model="decoder", seed=3)
    rows, tracer = [], get_tracer()
    t0 = tracer.now_us()
    api = FedAvgAPI(cfg, data, model, task="nwp", log_fn=rows.append)
    api.train()
    flushes = [dict(e.attrs) for e in tracer.events() if e.ts_us >= t0 and e.name == "flush"]
    return rows, flushes, flatten(api.global_vars["params"])


def test_a_federated_round_is_the_same_under_vmap_and_scan():
    """The stack through ``FedAvgAPI.train()`` under both client schedules:
    the same parameters (float32, the order of sums aside), and the
    ``flush`` spans carry the scan's and the differential maps' constants."""
    rows_v, flushes_v, params_v = one_round("vmap")
    _, flushes_s, params_s = one_round("scan")
    for name in params_v:
        assert jnp.allclose(params_v[name], params_s[name], rtol=0, atol=2e-6), name
    for flushes in (flushes_v, flushes_s):
        a = flushes[0]
        assert (a["attn_sites"], a["attn_kernel_sites"]) == (6, 0)
        assert (a["sscan_sites"], a["sscan_kernel_sites"], a["sscan_inner"]) == (1, 0, 128)
        assert (a["diff_pairs"], a["diff_key_dim"], a["diff_value_dim"]) == (2, 16, 32)
        assert "rope_sites" not in a and "ssd_sites" not in a and "moe_calls" not in a
    losses = [r["Train/Loss"] for r in rows_v if "Train/Loss" in r]
    assert len(losses) == 2 and losses[1] < losses[0]


@pytest.mark.parametrize("window", [None, 128], ids=["causal", "window"])
def test_values_wider_than_keys_through_the_kernel_match_the_plain_form(window):
    """A differential map as the layers hand it over: 4 query heads on 2
    key/value heads of 64, values of 128, at the kernel's shortest length,
    the kernel interpreted (q and k padded to the values' width, one head a
    lane tile) against the plain form: the output and the three gradients,
    both exact float32 that differ by the order of their sums (the padding's
    zero lanes add nothing): 2e-5 of the largest entry."""
    from fedml_tpu.ops.attention import attention
    from fedml_tpu.ops.flash_attention import flash_attention_bthd
    from fedml_tpu.parallel.ring_attention import full_attention

    B, T, H, KV, D, V = 1, 256, 4, 2, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q, k = (jax.random.normal(kk, (B, T, h, D)) for kk, h in zip(ks[:2], (H, KV)))
    v = jax.random.normal(ks[2], (B, T, KV, V))
    cot = jax.random.normal(ks[3], (B, T, H, V))
    assert takes_kernel(T, H, KV, D, 0, V) and not takes_kernel(T, H, KV, D, 0, 96)

    def run(form):
        def loss(q, k, v):
            out = form(q, k, v, causal=True, window=window)
            return jnp.sum(out * cot), out
        step = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        (_, out), grads = jax.jit(step)(q, k, v)
        return (out,) + grads

    for got, want in zip(run(flash_attention_bthd), run(full_attention)):
        assert got.shape == want.shape
        scale = float(jnp.max(jnp.abs(want)))
        assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * scale
    # the entry takes the kernel at this shape
    assert jnp.allclose(run(attention)[0], run(flash_attention_bthd)[0], atol=0, rtol=0)
