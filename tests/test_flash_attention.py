"""Pallas flash attention (ops/flash_attention.py) vs plain softmax
attention: forward exactness and full VJP (dq/dk/dv) through the custom
backward kernels. Runs in interpret mode on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.ops import flash_attention


def _ref_attention(q, k, v, causal):
    d = q.shape[-1]
    s = jnp.einsum("...qd,...kd->...qk", q, k) / jnp.sqrt(jnp.float32(d))
    if causal:
        S, Sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((S, Sk), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v)


def _qkv(shape, seed=0):
    rng = np.random.default_rng(seed)
    q, k, v = (
        jnp.asarray(rng.normal(size=shape).astype(np.float32)) for _ in range(3)
    )
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v = _qkv((2, 2, 128, 32))  # [B, H, S, d]
    out = flash_attention(q, k, v, causal=causal, chunk=64)
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_uneven_blocks_and_single_block():
    q, k, v = _qkv((1, 192, 16), seed=3)
    out = flash_attention(q, k, v, causal=True, chunk=64)
    ref = _ref_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # S smaller than the chunk: the chunk clamps to S
    q, k, v = _qkv((1, 32, 16), seed=4)
    out = flash_attention(q, k, v, causal=False)
    ref = _ref_attention(q, k, v, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_vjp_matches_reference(causal):
    q, k, v = _qkv((2, 128, 32), seed=7)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, chunk=64)
        return jnp.sum(jnp.sin(out))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(_ref_attention(q, k, v, causal)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5,
            err_msg=f"d{name} mismatch",
        )


def test_transformer_lm_with_flash_attention():
    """flash_attention_bthd is a drop-in attn_fn for TransformerLM: logits
    and gradients match the full-attention module."""
    from fedml_tpu.models.transformer import TransformerLM
    from fedml_tpu.ops import flash_attention_bthd

    V, B, T = 50, 2, 128
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, V, size=(B, T)), jnp.int32)

    def make(attn_fn=None):
        kw = dict(vocab_size=V, num_layers=1, num_heads=2, embed_dim=32,
                  max_len=T)
        if attn_fn is not None:
            kw["attn_fn"] = attn_fn
        return TransformerLM(**kw)

    ref_model = make()
    params = ref_model.init(jax.random.PRNGKey(0), tokens)
    flash_model = make(
        lambda q, k, v: flash_attention_bthd(q, k, v, chunk=64)
    )
    ref_logits = ref_model.apply(params, tokens)
    flash_logits = flash_model.apply(params, tokens)
    np.testing.assert_allclose(
        np.asarray(flash_logits), np.asarray(ref_logits), atol=1e-4
    )

    def loss(model, p):
        logits = model.apply(p, tokens)
        return jnp.mean(
            jnp.sum(jax.nn.log_softmax(logits) ** 2, axis=-1)
        )

    g_ref = jax.grad(lambda p: loss(ref_model, p))(params)
    g_flash = jax.grad(lambda p: loss(flash_model, p))(params)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_ref), jax.tree_util.tree_leaves(g_flash)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_shape_guards():
    q, k, v = _qkv((1, 100, 16))
    with pytest.raises(ValueError):
        flash_attention(q, k, v, chunk=64)
    q, k, v = _qkv((1, 128, 16))
    k2 = k[:, :64]
    with pytest.raises(ValueError):
        flash_attention(q, k2, v[:, :64], causal=True, chunk=64)
