"""The rotate-half rotary operator (ops/rotary.py) against the three-line
form it replaced, kept here as the plain reference: forward and gradient, for
heads of 128 and of 64, default and YaRN tables, float32 and bfloat16, under
``vmap`` and inside a ``scan`` as the two client schedules run it; the shape
decision; the fall to the plain form; and the decoder's ``rope_sites``.

On the last bit. The operator promises float32 products and a float32 sum,
rounded once. XLA's CPU backend contracts a product and a sum into one fused
multiply-add where it finds them in one fusion (the jitted three-line form
is contracted here, the interpreted kernel is not), which the chip's vector
unit does not do. So the last-bit cases run on numbers whose products are
exact in float32 — x and the tables rounded through bfloat16, 8 bits times 8
bits — where a contracted and an uncontracted sum are the same number and
every difference left is a wrong lane, sign or table row. With the tables
at full precision the two are held to one rounding of the dtype, and
``chip_smoke.py``'s ``kernels`` phase asks for the last bit on the chip."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import client_axis_map
from fedml_tpu.models import create_model
from fedml_tpu.models.decoder import rotary_tables
from fedml_tpu.ops import rotary as op

ROPES = {
    "default": {"rope_type": "default", "rope_theta": 500000},
    "yarn": {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
             "original_max_position_embeddings": 64, "attention_factor": 1.2772588722239782},
}
# [B, T, H, D]: a head a lane tile (Mellum's), two heads a tile (LFM2's), and
# both over more than one block of rows and of lanes
SHAPES = {"heads_of_128": (2, 256, 3, 128), "heads_of_64": (1, 512, 4, 64)}


def plain(x, cos, sin):
    """Rotate-half rotary on x [B, T, H, D], in float32, back in x's dtype:
    ``models/decoder.apply_rotary`` as it stood before the operator."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos[None, :, None, :] + rotated * sin[None, :, None, :]).astype(x.dtype)


def through_bfloat16(a):
    return a.astype(jnp.bfloat16).astype(a.dtype)


def case(shape, rope, dtype, exact, seed=0):
    B, T, H, D = shape
    cos, sin = rotary_tables(ROPES[rope], D, T)
    x, g = (jax.random.normal(jax.random.PRNGKey(seed + i), shape, jnp.float32) for i in (0, 1))
    if exact:
        cos, sin, x, g = (through_bfloat16(a) for a in (cos, sin, x, g))
    return x.astype(dtype), g.astype(dtype), cos, sin


def out_and_gradient(fn, x, g, cos, sin):
    out, pull = jax.vjp(lambda x: fn(x, cos, sin), x)
    return out, pull(g)[0]


def one_rounding(x, cos, sin, dtype):
    """What one rounding of ``dtype`` may move a float32 sum of the two
    products by, element by element."""
    x32 = np.abs(np.asarray(x, np.float32))
    half = x.shape[-1] // 2
    partner = np.concatenate([x32[..., half:], x32[..., :half]], axis=-1)
    reach = x32 * np.abs(cos)[None, :, None, :] + partner * np.abs(sin)[None, :, None, :]
    return float(jnp.finfo(dtype).eps) * reach


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rope", list(ROPES))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_forward_and_gradient_equal_the_plain_form_to_the_last_bit(shape, rope, dtype):
    x, g, cos, sin = case(SHAPES[shape], rope, dtype, exact=True)
    assert op.takes_kernel(*SHAPES[shape][1:])
    got = jax.jit(functools.partial(out_and_gradient, op.rotary))(x, g, cos, sin)
    want = jax.jit(functools.partial(out_and_gradient, plain))(x, g, cos, sin)
    for name, a, b in zip(("out", "dx"), got, want):
        assert a.dtype == b.dtype == dtype and a.shape == b.shape
        assert bool(jnp.all(a == b)), (name, int(jnp.sum(a != b)))
    assert float(jnp.max(jnp.abs(got[0].astype(jnp.float32) - x.astype(jnp.float32)))) > 0.1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("rope", list(ROPES))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_at_full_precision_tables_both_are_within_one_rounding_of_the_dtype(shape, rope, dtype):
    x, g, cos, sin = case(SHAPES[shape], rope, dtype, exact=False, seed=5)
    got = jax.jit(functools.partial(out_and_gradient, op.rotary))(x, g, cos, sin)
    want = jax.jit(functools.partial(out_and_gradient, plain))(x, g, cos, sin)
    cos, sin = np.asarray(cos), np.asarray(sin)
    for name, a, b, operand in zip(("out", "dx"), got, want, (x, g)):
        gap = np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))
        assert np.all(gap <= one_rounding(operand, cos, sin, dtype)), (name, float(gap.max()))


def test_the_rule_keeps_the_tables_and_nothing_of_x():
    x, _, cos, sin = case(SHAPES["heads_of_128"], "yarn", jnp.bfloat16, exact=True)
    _, pull = jax.vjp(lambda x: op.rotary(x, cos, sin), x)
    kept = jax.tree_util.tree_leaves(pull)
    assert sorted(a.shape for a in kept) == [(256, 128), (256, 128)]
    assert all(a.dtype == jnp.float32 for a in kept)


@pytest.mark.parametrize("schedule", ["vmap", "scan"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_under_the_client_axis_both_schedules_equal_the_plain_form(shape, schedule):
    """``client_axis_map``'s two schedules: a batched client axis in front
    of the kernel's grid, and the kernel inside a ``scan``'s body."""
    clients = 3
    _, _, cos, sin = case(SHAPES[shape], "yarn", jnp.bfloat16, exact=True)
    xs, gs = (through_bfloat16(jax.random.normal(
        jax.random.PRNGKey(7 + i), (clients,) + SHAPES[shape], jnp.float32)).astype(jnp.bfloat16)
        for i in (0, 1))

    def over_clients(fn):
        local = lambda tables, x, g: out_and_gradient(fn, x, g, *tables)
        return jax.jit(client_axis_map(local, schedule))((cos, sin), xs, gs)

    for a, b in zip(over_clients(op.rotary), over_clients(plain)):
        assert a.shape == (clients,) + SHAPES[shape] and bool(jnp.all(a == b))


def kernel_calls(fn, *args):
    def count(jaxpr):
        total = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                total.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                total += count(sub)
        return total

    return sorted(count(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize("T,H,D,takes", [
    (2048, 32, 128, True),   # mellum2-12b-a2.5b.silo2's q
    (2048, 4, 128, True),    # and its k
    (4096, 32, 64, True),    # lfm2-8b-a1b.silo2t4k's q: two heads a lane tile
    (4096, 8, 64, True),     # and its k
    (256, 8, 32, True),      # four heads a tile
    (64, 32, 128, False),    # the evaluation documents
    (2000, 32, 128, False),  # no whole number of row blocks
    (2048, 1, 64, False),    # one shared rotary key of 64: half a tile
    (2048, 3, 64, False),    # three heads of 64: a tile and a half
    (2048, 32, 192, False),  # a head wider than a tile
    (2048, 32, 96, False),   # heads that do not share a tile evenly
    (32, 4, 16, False),      # a rehearsal's shapes
])
def test_the_decision_is_of_shapes_alone_and_the_program_follows(T, H, D, takes):
    assert op.takes_kernel(T, H, D) is takes
    if T <= 256:
        cos, sin = rotary_tables(ROPES["default"], D, T)
        x = jax.ShapeDtypeStruct((1, T, H, D), jnp.bfloat16)
        forward = lambda x: op.rotary(x, cos, sin)
        both = lambda x: jax.vjp(forward, x)[1](x)
        assert kernel_calls(forward, x) == (["rotary_fwd"] if takes else [])
        assert kernel_calls(both, x) == (["rotary_bwd", "rotary_fwd"] if takes else [])


@pytest.mark.parametrize("shape", [(2, 64, 4, 128), (1, 256, 3, 64), (2, 32, 4, 16)])
def test_a_shape_the_kernel_refuses_is_the_plain_form(shape):
    assert not op.takes_kernel(*shape[1:])
    x, g, cos, sin = case(shape, "yarn", jnp.bfloat16, exact=False)
    got = jax.jit(functools.partial(out_and_gradient, op.rotary))(x, g, cos, sin)
    want = jax.jit(functools.partial(out_and_gradient, plain))(x, g, cos, sin)
    assert all(bool(jnp.all(a == b)) for a, b in zip(got, want))
    trace = lambda fn: str(jax.make_jaxpr(functools.partial(out_and_gradient, fn))(x, g, cos, sin))
    assert trace(op.rotary) == trace(plain)


DECODERS = {
    "grouped_query": (dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
                           head_dim=64, num_hidden_layers=2), ((4, 64), (2, 64)) * 2),
    "conv_between": (dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2,
                          head_dim=16, layer_types=["conv", "full_attention", "conv"],
                          conv_L_cache=3, use_qk_norm=True), ((4, 16), (2, 16))),
    "latent_halves": (dict(hidden_size=32, num_attention_heads=2, num_hidden_layers=2,
                           kv_lora_rank=24, q_lora_rank=None, qk_nope_head_dim=16,
                           qk_rope_head_dim=8, v_head_dim=16), ((2, 8), (1, 8)) * 2),
    "latent_pairs": (dict(hidden_size=32, num_attention_heads=2, num_hidden_layers=2,
                          kv_lora_rank=24, q_lora_rank=None, qk_nope_head_dim=16,
                          qk_rope_head_dim=8, v_head_dim=16, rope_interleave=True), ()),
}


@pytest.mark.parametrize("name", list(DECODERS))
def test_rope_sites_are_what_the_traced_layers_hand_the_operator(name, monkeypatch):
    import fedml_tpu.models.decoder as decoder

    spec, sites = DECODERS[name]
    seen = []

    def spy(x, cos, sin):
        assert cos.shape == sin.shape == (x.shape[1], x.shape[3])
        seen.append(x.shape[2:])
        return x

    monkeypatch.setattr(decoder, "rotary", spy)
    model = create_model("decoder", "random_tokens", (24,), 61, **spec)
    jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert tuple(seen) == model.module.rope_sites() == sites
    assert "rope_sites" not in create_model("transformer", "random_tokens", (24,), 61,
                                            num_layers=1, num_heads=2, embed_dim=32).flush_attrs(1)
