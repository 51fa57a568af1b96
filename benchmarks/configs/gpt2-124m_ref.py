"""Plain reference of the GPT-2 decoder (Radford et al. 2019; Hugging Face
``openai-community/gpt2``): learned token and position embeddings, pre-LN
blocks of causal multi-head attention and a GELU (tanh form) MLP of width
4d, a final layer norm and an output head.

Departures from the published model, each the program's and followed here:
the output head is a matrix of its own, not the token embedding transposed;
the attention projections carry no bias; layer-norm epsilon is 1e-6."""

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def _dims(cfg):
    m = cfg["model"]
    kw = m["kwargs"]
    return (int(m["num_classes"]), int(m["input_shape"][0]), int(kw["num_layers"]),
            int(kw["num_heads"]), int(kw["embed_dim"]))


def param_shapes(cfg):
    V, T, L, H, d = _dims(cfg)
    shapes = {
        "tok_embed/embedding": (V, d), "pos_embed": (T, d),
        "ln_f/scale": (d,), "ln_f/bias": (d,), "head/kernel": (d, V),
    }
    for i in range(L):
        b = f"block{i}/"
        shapes.update({
            b + "ln1/scale": (d,), b + "ln1/bias": (d,),
            b + "qkv/kernel": (d, 3 * d), b + "proj/kernel": (d, d),
            b + "ln2/scale": (d,), b + "ln2/bias": (d,),
            b + "mlp_up/kernel": (d, 4 * d), b + "mlp_up/bias": (4 * d,),
            b + "mlp_down/kernel": (4 * d, d), b + "mlp_down/bias": (d,),
        })
    return shapes


@functools.lru_cache(maxsize=4)
def _maker(shapes):
    """One jitted call that draws every leaf from a key."""

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes):
            if name.endswith("/scale"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith("/bias"):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = 0.02 * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return make


def init_params(seed, cfg):
    make = _maker(tuple(sorted(param_shapes(cfg).items())))
    return make(jax.random.fold_in(jax.random.PRNGKey(seed % (2**31 - 1)), 7919))


def _ln(x, scale, bias):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + LN_EPS)
    return (y * scale + bias).astype(x.dtype)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def logits_fn(p, tokens, ops, cfg):
    V, _, L, H, d = _dims(cfg)
    B, T = tokens.shape
    D = d // H
    x = p["tok_embed/embedding"][tokens] + p["pos_embed"][:T]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(L):
        b = f"block{i}/"
        h = _ln(x, p[b + "ln1/scale"], p[b + "ln1/bias"])
        q, k, v = jnp.split(ops.dot(h, p[b + "qkv/kernel"]), 3, axis=-1)
        q, k, v = (a.reshape(B, T, H, D) for a in (q, k, v))
        s = ops.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(D)
        s = jnp.where(causal[None, None], s, -1e30)
        a = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o = ops.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, T, d)
        x = x + ops.dot(o, p[b + "proj/kernel"])
        h = _ln(x, p[b + "ln2/scale"], p[b + "ln2/bias"])
        h = _gelu(ops.dot(h, p[b + "mlp_up/kernel"]) + p[b + "mlp_up/bias"])
        x = x + ops.dot(h, p[b + "mlp_down/kernel"]) + p[b + "mlp_down/bias"]
    x = _ln(x, p["ln_f/scale"], p["ln_f/bias"])
    return ops.dot(x, p["head/kernel"])


def unit_batch(cfg):
    """Shapes of one real document, for the FLOP count."""
    _, T, _, _, _ = _dims(cfg)
    return (
        jax.ShapeDtypeStruct((1, T), jnp.int32),
        jax.ShapeDtypeStruct((1, T), jnp.int32),
    )
