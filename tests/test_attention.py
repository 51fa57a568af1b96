"""The attention entry (ops/attention.py): the blockwise kernel against the
plain form over the masks, head layouts and widths the two language-model
cells send (interpret mode on the CPU, small shapes), under ``vmap`` and
inside a ``scan`` as the two client schedules run it; the dispatch rule; the
two models through either form; and the ``flush`` span's two attributes."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.models import create_model
from fedml_tpu.ops.attention import attention, takes_kernel
from fedml_tpu.ops.flash_attention import flash_attention_bthd
from fedml_tpu.parallel.ring_attention import full_attention

T, BLOCK = 128, 64
MASKS = {  # (causal, window)
    "causal": (True, None),
    "window_under_a_block": (True, 40),
    "window_of_a_block": (True, 64),
    "window_over_a_block": (True, 100),
}
HEADS = {"equal_heads": (2, 2), "four_query_heads_a_kv_head": (4, 1)}


def _qkv(B, H, KV, D, seed=0, length=T):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.normal(size=(B, length, heads, D)), jnp.float32)
        for heads in (H, KV, KV)
    )


def _kernel(q, k, v, causal, window):
    return flash_attention_bthd(
        q, k, v, causal=causal, window=window, chunk=BLOCK)


def _out_and_grads(fn, qkv):
    def loss(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(jnp.sin(out)), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(*qkv)
    return (out,) + grads


def _assert_close(got, want):
    # tests/test_flash_attention.py's pins: 2e-5 forward, 5e-5 gradients
    for a, b, name, atol in zip(got, want, ("out", "dq", "dk", "dv"), (2e-5, 5e-5, 5e-5, 5e-5)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=atol, err_msg=f"{name} mismatch"
        )


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("heads", list(HEADS))
@pytest.mark.parametrize("mask", list(MASKS))
def test_kernel_forward_and_gradient_match_the_plain_form(mask, heads, head_dim):
    causal, window = MASKS[mask]
    qkv = _qkv(2, *HEADS[heads], head_dim)
    _assert_close(
        _out_and_grads(functools.partial(_kernel, causal=causal, window=window), qkv),
        _out_and_grads(functools.partial(full_attention, causal=causal, window=window), qkv),
    )


@pytest.mark.parametrize("schedule", ["vmap", "scan"])
@pytest.mark.parametrize(
    "heads,head_dim,mask",
    [("equal_heads", 64, "causal"), ("four_query_heads_a_kv_head", 128, "window_under_a_block")],
)
def test_kernel_under_the_client_schedules(schedule, heads, head_dim, mask):
    """Forward and gradient with a leading client axis: batched by ``vmap``
    (silo4's schedule) and one client after another in a ``scan`` (silo2's)."""
    causal, window = MASKS[mask]
    clients = [_qkv(1, *HEADS[heads], head_dim, seed=s) for s in range(3)]
    stacked = tuple(jnp.stack(parts) for parts in zip(*clients))

    def over_clients(fn):
        one = lambda q, k, v: _out_and_grads(functools.partial(fn, causal=causal, window=window),
                                             (q, k, v))
        if schedule == "vmap":
            return jax.vmap(one)(*stacked)
        return jax.lax.scan(lambda _, qkv: (None, one(*qkv)), None, stacked)[1]

    _assert_close(over_clients(_kernel), over_clients(full_attention))


def _pallas_calls(fn, *args, kernel="attention_"):
    """The calls of the kernels named ``kernel...`` in the traced program
    (``ops/rotary.py``'s carry another name)."""

    def count(jaxpr):
        total = 0
        for eqn in jaxpr.eqns:
            total += eqn.primitive.name == "pallas_call" and eqn.params["name"].startswith(kernel)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                total += count(sub)
        return total

    return count(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize(
    "length,heads,kv_heads,head_dim,kernel",
    [
        (64, 12, 12, 64, False),    # the evaluation documents
        (255, 12, 12, 64, False),   # no whole number of chunks
        (8192, 12, 12, 64, False),  # longer than a program instance holds
        (256, 12, 12, 64, True),
        (1024, 12, 12, 64, True),   # gpt2-124m.silo4
        (2048, 32, 4, 128, True),   # mellum2-12b-a2.5b.silo2
        (256, 4, 2, 64, True),      # two heads a tile, both of one K/V head
        (256, 8, 4, 32, False),     # four heads a tile, two a K/V head
        (256, 6, 4, 64, False),     # K/V heads do not divide the query heads
    ],
)
def test_dispatch_is_decided_by_the_shapes_and_the_program_follows(
        length, heads, kv_heads, head_dim, kernel):
    assert takes_kernel(length, heads, kv_heads, head_dim) is kernel
    if heads % kv_heads == 0 and length <= 256:
        q, k, v = (jax.ShapeDtypeStruct((1, length, h, head_dim), jnp.float32)
                   for h in (heads, kv_heads, kv_heads))
        calls = _pallas_calls(functools.partial(attention, causal=True), q, k, v)
        assert calls == (1 if kernel else 0)


LM_LENGTH, VOCAB = 256, 61
DECODER = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=64,
    layer_types=["sliding_attention", "full_attention"], sliding_window=100,
    rope_parameters={kind: {"rope_type": "default", "rope_theta": 500000}
                     for kind in ("full_attention", "sliding_attention")},
    num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16,
    norm_topk_prob=True, rms_norm_eps=1e-6, tie_word_embeddings=False,
)
MODELS = {
    "transformer": dict(num_layers=2, num_heads=2, embed_dim=128),
    "decoder": DECODER,
}


def _model(name, length=LM_LENGTH):
    return create_model(name, "random_tokens", (length,), VOCAB, **MODELS[name])


@pytest.mark.parametrize("name", list(MODELS))
def test_models_give_the_same_loss_and_gradients_through_either_form(name, monkeypatch):
    model = _model(name)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, VOCAB, size=(2, LM_LENGTH)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0))

    def loss(variables):
        logits, _ = model.apply(variables, tokens, train=True)
        return jnp.mean(jnp.sum(jax.nn.log_softmax(logits) ** 2, axis=-1))

    assert _pallas_calls(loss, variables) == 2  # both layers take the kernel
    through_kernel = jax.value_and_grad(loss)(variables)
    # the plain form in the entry's place, for every caller
    import fedml_tpu.ops.attention as entry
    monkeypatch.setattr(entry, "takes_kernel", lambda *shape: False)
    assert _pallas_calls(lambda v: loss(v), variables) == 0  # a fresh function: no cached trace
    plain = jax.value_and_grad(loss)(variables)
    for a, b in zip(jax.tree_util.tree_leaves(through_kernel), jax.tree_util.tree_leaves(plain)):
        # tests/test_flash_attention.py's pin for a model through both forms
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def _api(name, length):
    from fedml_tpu.algorithms import FedAvgAPI
    from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
    from fedml_tpu.data.base import FederatedDataset

    docs = np.random.default_rng(0).integers(1, VOCAB, size=(2, 2, length + 1), dtype=np.int32)
    data = FederatedDataset(
        name="random_tokens", client_x=list(docs[:, :, :-1]), client_y=list(docs[:, :, 1:]),
        test_x=docs[0, :2, :-1], test_y=docs[0, :2, 1:], num_classes=VOCAB)
    cfg = RunConfig(
        data=DataConfig(batch_size=2, pad_bucket=1),
        fed=FedConfig(client_num_in_total=2, client_num_per_round=2, comm_round=1, epochs=1),
        train=TrainConfig(client_optimizer="sgd", lr=0.05), model=name, seed=1,
    )
    return FedAvgAPI(cfg, data, _model(name, length), task="nwp", log_fn=lambda row: None)


@pytest.mark.parametrize("name,length,kernel_sites,rope", [
    ("transformer", 256, 2, {}), ("transformer", 64, 0, {}),
    # q and k of both layers go through the rotate-half operator
    # and nine grouped products and two slot sums of each expert layer and
    # the token table's gradient, at widths under a lane tile
    ("decoder", 256, 2, {"rope_kernel_sites": 4, "rope_sites": 4,
                         "moe_kernel_sites": 0, "moe_grouped_sites": 18,
                         "moe_slot_kernel_sites": 0, "moe_slot_sites": 4,
                         "embed_kernel_sites": 0, "embed_sites": 1}),
    ("decoder", 48, 0, {"rope_kernel_sites": 0, "rope_sites": 4,
                        "moe_kernel_sites": 0, "moe_grouped_sites": 18,
                        "moe_slot_kernel_sites": 0, "moe_slot_sites": 4,
                        "embed_kernel_sites": 0, "embed_sites": 1}),
])
def test_what_the_api_reports_is_what_the_traced_program_contains(name, length, kernel_sites, rope):
    api = _api(name, length)
    sites = {k: v for k, v in api._flush_attrs.items() if k.endswith("sites")}
    assert sites == {"attn_kernel_sites": kernel_sites, "attn_sites": 2, **rope}
    tokens = jax.ShapeDtypeStruct((2, length), jnp.int32)
    forward = lambda variables, x: api.model.apply(variables, x, train=True)[0]
    assert _pallas_calls(forward, api.global_vars, tokens) == kernel_sites
    assert _pallas_calls(forward, api.global_vars, tokens, kernel="rotary_") == rope.get(
        "rope_kernel_sites", 0)


def test_flush_span_carries_the_two_attributes_and_a_model_without_attention_none():
    from fedml_tpu.telemetry import get_tracer

    tracer = get_tracer()
    t0 = tracer.now_us()
    _api("transformer", 64).train()
    flushes = [e.attrs for e in tracer.events() if e.name == "flush" and e.ts_us >= t0]
    assert flushes and all(
        (a["attn_kernel_sites"], a["attn_sites"]) == (0, 2) for a in flushes)
    # no state-space layer, no scan sites
    assert not any(k.startswith("ssd_") for a in flushes for k in a)
