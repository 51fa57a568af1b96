"""Plain reference of the FedAvg CNN (McMahan et al. 2017, as FedML's
CNNOriginalFedAvg): conv 5x5x32 SAME, ReLU, max-pool 2x2, conv 5x5x64 SAME,
ReLU, max-pool 2x2, dense 512, ReLU, dense 62. NHWC. 1 690 046 parameters."""

import functools

import jax
import jax.numpy as jnp


def param_shapes(cfg):
    classes = int(cfg["model"]["num_classes"])
    return {
        "conv2d_1/kernel": (5, 5, 1, 32), "conv2d_1/bias": (32,),
        "conv2d_2/kernel": (5, 5, 32, 64), "conv2d_2/bias": (64,),
        "linear_1/kernel": (7 * 7 * 64, 512), "linear_1/bias": (512,),
        "linear_2/kernel": (512, classes), "linear_2/bias": (classes,),
    }


@functools.lru_cache(maxsize=4)
def _maker(shapes):
    """One jitted call that draws every leaf from a key."""

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes):
            if name.endswith("/bias"):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                fan_in = 1
                for d in shape[:-1]:
                    fan_in *= d
                out[name] = jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32
                ) / jnp.sqrt(jnp.float32(fan_in))
        return out

    return make


def init_params(seed, cfg):
    make = _maker(tuple(sorted(param_shapes(cfg).items())))
    return make(jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)), 7919))


def _pool(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
    )


def logits_fn(p, x, ops, cfg):
    h = jax.nn.relu(ops.conv(x, p["conv2d_1/kernel"]) + p["conv2d_1/bias"])
    h = _pool(h)
    h = jax.nn.relu(ops.conv(h, p["conv2d_2/kernel"]) + p["conv2d_2/bias"])
    h = _pool(h)
    h = h.reshape((h.shape[0], -1))
    h = jax.nn.relu(ops.dot(h, p["linear_1/kernel"]) + p["linear_1/bias"])
    return ops.dot(h, p["linear_2/kernel"]) + p["linear_2/bias"]


def unit_batch(cfg):
    """Shapes of one real sample, for the FLOP count."""
    side = cfg["population"]["sample"]["side"]
    return (
        jax.ShapeDtypeStruct((1, side, side, 1), jnp.float32),
        jax.ShapeDtypeStruct((1,), jnp.int32),
    )
