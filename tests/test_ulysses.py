"""Ulysses all-to-all sequence parallelism (parallel/ulysses.py): exactness
vs full attention on the 8-device mesh, and the SP LM train step under
sp_impl=ulysses matches sp_impl=ring (both are exact attention, so one
training step must agree to fp tolerance)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedml_tpu.parallel.ring_attention import full_attention, make_ring_attention
from fedml_tpu.parallel.ulysses import make_ulysses_attention


def _mesh(n=8):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    return Mesh(np.array(jax.devices()[:n]), ("seq",))


def _qkv(B, T, H, D, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
        for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full_attention(causal):
    mesh = _mesh()
    B, T, H, D = 2, 64, 8, 16  # H divisible by 8 shards
    q, k, v = _qkv(B, T, H, D)
    spec = NamedSharding(mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    out = make_ulysses_attention(mesh, causal=causal)(qs, ks, vs)
    ref = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_ulysses_matches_ring():
    mesh = _mesh()
    q, k, v = _qkv(1, 64, 8, 16, seed=3)
    spec = NamedSharding(mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    u = make_ulysses_attention(mesh, causal=True)(qs, ks, vs)
    r = make_ring_attention(mesh, causal=True)(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(u), np.asarray(r), atol=2e-5)


def test_ulysses_with_flash_core():
    """The Pallas flash kernel as the per-device attention core under
    ulysses (the long-context configuration: all-to-all reshard + blockwise
    local attention, no T×T materialisation anywhere)."""
    from fedml_tpu.ops import flash_attention_bthd

    mesh = _mesh()
    B, T, H, D = 1, 128, 8, 16
    q, k, v = _qkv(B, T, H, D, seed=5)
    spec = NamedSharding(mesh, P(None, "seq", None, None))
    qs, ks, vs = (jax.device_put(x, spec) for x in (q, k, v))
    out = make_ulysses_attention(
        mesh,
        causal=True,
        attn_fn=lambda q, k, v, causal: flash_attention_bthd(
            q, k, v, causal=causal, chunk=64
        ),
    )(qs, ks, vs)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_sp_train_step_ring_vs_ulysses():
    from fedml_tpu.parallel.long_context import make_sp_train_step

    mesh = _mesh()
    V, B, T = 64, 2, 64
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, V, size=(B, T)), jnp.int32
    )
    targets = jnp.roll(tokens, -1, axis=1)
    results = {}
    for impl in ("ring", "ulysses"):
        init_fn, step = make_sp_train_step(
            mesh, V, lr=1e-3, sp_impl=impl,
            num_layers=1, num_heads=8, embed_dim=32, max_len=T,
        )
        params, opt_state = init_fn(jax.random.PRNGKey(0), tokens)
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        results[impl] = (params, float(loss))
    assert results["ring"][1] == pytest.approx(results["ulysses"][1], rel=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(results["ring"][0]),
        jax.tree_util.tree_leaves(results["ulysses"][0]),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        )
