"""ops/pooling.max_pool against flax's ``nn.max_pool``: the same value and
the same gradient TO THE LAST BIT, ties included (``select_and_scatter``
gives a window's gradient to its first maximum in row-major order), alone
and under the transforms the round program puts around the model."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from fedml_tpu.models import create_model
from fedml_tpu.models.cnn import CNNOriginalFedAvg
from fedml_tpu.ops.pooling import max_pool

CELL_SHAPES = [(20, 28, 28, 32), (20, 14, 14, 64)]  # the FEMNIST cells' two pools
ODD_SHAPES = [(3, 28, 28, 32), (7, 14, 14, 64), (1, 4, 6, 5)]


def _bits(a):
    """An array as its bit patterns, so that -0.0 != 0.0 and nan == nan."""
    a = jnp.asarray(a)
    unsigned = {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]
    return np.asarray(lax.bitcast_convert_type(a, unsigned))


def _assert_same_bits(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want), strict=True):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(_bits(g), _bits(w))


def _input(kind, shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    if kind == "random":
        x = rng.normal(size=shape)
    elif kind == "relu":  # what the model pools: most windows all zero
        x = np.maximum(rng.normal(size=shape) - 1.5, 0.0)
    elif kind == "constant":  # every window fully tied
        x = np.full(shape, 0.75)
    elif kind == "few_values":  # ties at every position of a window
        x = rng.integers(0, 2, size=shape).astype(np.float64)
    elif kind == "inf":
        x = rng.normal(size=shape)
        u = rng.random(shape)
        x = np.where(u < 0.2, np.inf, np.where(u < 0.4, -np.inf, x))
    else:
        raise AssertionError(kind)
    return jnp.asarray(x, dtype)


def _cotangent(shape, dtype, window=(2, 2), seed=1):
    *lead, H, W, C = shape
    out = (*lead, H // window[0], W // window[1], C)
    return jnp.asarray(np.random.default_rng(seed).normal(size=out), dtype)


def _flax_pool(x, window=(2, 2)):
    return nn.max_pool(x, window, strides=window)


def _value_and_grad(pool, x, dy):
    y, vjp = jax.vjp(pool, x)
    return y, vjp(dy)[0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["random", "relu", "constant", "few_values", "inf"])
@pytest.mark.parametrize("shape", CELL_SHAPES + ODD_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_value_and_gradient_bit_equal_to_flax(shape, kind, dtype):
    x, dy = _input(kind, shape, dtype), _cotangent(shape, dtype)
    _assert_same_bits(
        _value_and_grad(lambda x: max_pool(x, (2, 2), strides=(2, 2)), x, dy),
        _value_and_grad(_flax_pool, x, dy),
    )


@pytest.mark.parametrize("window", [(2, 2), (1, 2), (2, 1), (3, 2), (2, 3), (3, 3)],
                         ids=lambda w: "x".join(map(str, w)))
@pytest.mark.parametrize("kind", ["random", "few_values"])
def test_any_window_that_tiles_the_input(window, kind):
    shape = (3, 12, 12, 8)
    x, dy = _input(kind, shape, jnp.float32), _cotangent(shape, jnp.float32, window)
    _assert_same_bits(
        _value_and_grad(lambda x: max_pool(x, window), x, dy),
        _value_and_grad(lambda x: _flax_pool(x, window), x, dy),
    )


def test_unbatched_input_as_flax_takes_it():
    x, dy = _input("few_values", (6, 4, 3), jnp.float32), _cotangent((6, 4, 3), jnp.float32)
    _assert_same_bits(
        _value_and_grad(lambda x: max_pool(x, (2, 2)), x, dy),
        _value_and_grad(_flax_pool, x, dy),
    )


def _loss(pool):
    return lambda x, w: jnp.sum(pool(jnp.maximum(x, 0.0)) * w)


def _under_vmap(pool, x, w):
    """Over a leading client axis, as the vmap schedule runs the model."""
    return jax.vmap(jax.value_and_grad(_loss(pool)))(x, w)


def _under_scan(pool, x, w):
    """A scan over steps whose carry the gradient feeds, as local training is."""

    def step(carry, xw):
        value, grad = jax.value_and_grad(_loss(pool))(xw[0] + carry, xw[1])
        return carry + 0.5 * grad[0], (value, grad)

    return lax.scan(step, jnp.zeros_like(x[0, 0]), (x, w))


def _under_cond(pool, x, w):
    """The skipped padding step: a cond whose real branch trains."""

    def one(x, w, real):
        return lax.cond(
            real,
            lambda: jax.value_and_grad(_loss(pool))(x, w),
            lambda: (jnp.zeros((), x.dtype), jnp.zeros_like(x)),
        )

    return [jax.jit(one)(x[0], w[0], real) for real in (True, False)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["random", "few_values"])
@pytest.mark.parametrize("under", [_under_vmap, _under_scan, _under_cond],
                         ids=["vmap", "scan", "cond"])
def test_bit_equal_under_the_round_programs_transforms(under, kind, dtype):
    shape = (4, 5, 8, 8, 16)  # clients or steps in front of [B, H, W, C]
    x, w = _input(kind, shape, dtype), _cotangent(shape, dtype)
    _assert_same_bits(
        under(lambda x: max_pool(x, (2, 2), strides=(2, 2)), x, w),
        under(_flax_pool, x, w),
    )


@pytest.mark.parametrize(
    "shape,window,strides",
    [
        ((2, 8, 8, 4), (3, 3), (2, 2)),    # resnet_gn's and darts' pools: overlapping
        ((2, 8, 8, 4), (2, 2), (1, 1)),
        ((2, 8, 8, 4), (2, 2), (4, 4)),    # gaps between the windows
        ((2, 9, 8, 4), (2, 2), (2, 2)),    # H is not a multiple of the window
        ((2, 8, 7, 4), (2, 2), None),
        ((2, 8, 8, 8, 4), (2, 2, 2), None),  # three spatial dims
        ((8, 4), (2, 2), None),
    ],
    ids=["3x3s2", "2x2s1", "2x2s4", "oddH", "oddW", "3d", "rank2"],
)
def test_a_window_that_does_not_tile_the_input_is_refused(shape, window, strides):
    with pytest.raises(ValueError, match="flax.linen.max_pool"):
        max_pool(jnp.zeros(shape), window, strides=strides)


def test_gradient_program_holds_no_select_and_scatter():
    x = jnp.zeros(CELL_SHAPES[0])
    ours = jax.jit(jax.value_and_grad(lambda x: jnp.sum(max_pool(x, (2, 2))))).lower(x).as_text()
    flax = jax.jit(jax.value_and_grad(lambda x: jnp.sum(_flax_pool(x)))).lower(x).as_text()
    assert "select_and_scatter" in flax
    assert "select_and_scatter" not in ours and "reduce_window" in ours


class _FlaxPoolTwin(CNNOriginalFedAvg):
    """CNNOriginalFedAvg as it was: the same layers around ``nn.max_pool``."""

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.relu(nn.Conv(32, (5, 5), padding="SAME", name="conv2d_1")(x))
        x = _flax_pool(x)
        x = nn.relu(nn.Conv(64, (5, 5), padding="SAME", name="conv2d_2")(x))
        x = _flax_pool(x)
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(512, name="linear_1")(x))
        return nn.Dense(self.num_classes, name="linear_2")(x)


@pytest.mark.parametrize("batch", [20, 7])
def test_femnist_cnn_logits_and_gradients_equal_its_flax_twin(batch):
    model = create_model("cnn", "femnist", (28, 28, 1), 62)
    twin = dataclasses.replace(model, module=_FlaxPoolTwin(num_classes=62))
    variables = model.init(jax.random.PRNGKey(0))
    _assert_same_bits(twin.init(jax.random.PRNGKey(0)), variables)
    rng = np.random.default_rng(batch)
    x = jnp.asarray(0.1 * rng.normal(size=(batch, 28, 28, 1)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 62, size=batch))

    def loss_and_logits(m):
        def f(variables):
            logits, _ = m.apply(variables, x, train=True)
            picked = jnp.take_along_axis(jax.nn.log_softmax(logits), labels[:, None], axis=1)
            return -jnp.mean(picked), logits

        return jax.value_and_grad(f, has_aux=True)(variables)

    # primitive by primitive: a compiler that fuses the two programs
    # differently may sum a bias gradient in another order
    with jax.disable_jit():
        _assert_same_bits(loss_and_logits(model), loss_and_logits(twin))
