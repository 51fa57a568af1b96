"""Ring attention — sequence/context parallelism over a mesh axis.

Green-field for the TPU build: the reference has NO sequence parallelism of
any kind (SURVEY §2g/§5 — its longest sequence is an 80-token Shakespeare
window), but long-context is first-class here. Design follows the public
ring-attention recipe (Liu et al. 2023; jax-ml scaling-book ch. "sharding"):
Q/K/V are sharded along the sequence axis of a Mesh; each device holds one
query block and, over N steps, sees every K/V block as they rotate around
the ring via `jax.lax.ppermute` over ICI. Softmax is computed online
(running max m, normalizer l, accumulator o — the flash-attention
recurrence), so the full T×T score matrix never materializes: memory is
O(T_local²) per device and the N rotations overlap compute with ICI
transfers (XLA pipelines ppermute with the block matmuls).

Exact: matches full attention to fp tolerance (test_ring_attention.py),
including causal masking via global block offsets."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG_INF = -1e30


def _block_attn(q, k, v, q_off, k_off, causal: bool, scale: float, o, m, l):
    """One online-softmax accumulation step.

    q [B, Tq, H, D], k/v [B, Tk, H, D]; o/m/l running state.
    Positions are global: q_off/k_off are the blocks' global start indices.
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    # scores [B, H, Tq, Tk]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        q_pos = q_off + jax.lax.iota(jnp.int32, Tq)
        k_pos = k_off + jax.lax.iota(jnp.int32, Tk)
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    s_max = jnp.max(s, axis=-1)  # [B, H, Tq]
    m_new = jnp.maximum(m, s_max)
    # all-masked guard: exp of (-inf − -inf); clamp the reference point
    m_safe = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
    alpha = jnp.where(m <= _NEG_INF, 0.0, jnp.exp(m - m_safe))
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(s <= _NEG_INF, 0.0, p)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v)
    return o_new, m_new, l_new


def ring_attention_sharded(q, k, v, axis_name: str, causal: bool = False):
    """The per-shard body (call inside shard_map over ``axis_name``).

    q, k, v: [B, T_local, H, D] — the local sequence block. Returns the
    attention output with the same shape.
    """
    B, Tq, H, D = q.shape
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    perm = [(j, (j + 1) % n) for j in range(n)]

    # initial accumulators are constants; mark them device-varying so the
    # fori_loop carry (mixed with sharded q/k/v) type-checks under
    # shard_map's varying-manual-axes rules
    pvary = lambda a: jax.lax.pcast(a, (axis_name,), to="varying")
    o0 = pvary(jnp.zeros((B, H, Tq, D), jnp.float32))
    m0 = pvary(jnp.full((B, H, Tq), _NEG_INF, jnp.float32))
    l0 = pvary(jnp.zeros((B, H, Tq), jnp.float32))
    q_off = my_idx * Tq

    def body(i, carry):
        o, m, l, kk, vv = carry
        # after i rotations, this device holds the block that originated at
        # ring position (my_idx − i) mod n
        k_off = ((my_idx - i) % n) * Tq
        o, m, l = _block_attn(q, kk, vv, q_off, k_off, causal, scale, o, m, l)
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return (o, m, l, kk, vv)

    o, m, l, _, _ = jax.lax.fori_loop(0, n, body, (o0, m0, l0, k, v))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def make_ring_attention(mesh: Mesh, axis_name: str = "seq", causal: bool = False):
    """jit-ready ring attention: [B, T, H, D] inputs sharded on T over the
    mesh axis; output sharded the same way."""
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(
            ring_attention_sharded, axis_name=axis_name, causal=causal
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return jax.jit(fn)  # fedlint: disable=uncached-jit -- bespoke ring-attention kernel wrapper closed over the mesh; built once per benchmark run


def full_attention(q, k, v, causal: bool = False, window: Optional[int] = None,
                   q_rope=None, k_rope=None, scale: Optional[float] = None):
    """Reference O(T²) attention: q [B, T, H, D], k [B, T, KV, D] and v
    [B, T, KV, V] (values may be wider than keys) -> [B, T, H, V].

    ``window`` keeps, beside the causal mask, only the keys with
    ``i - j < window`` (sliding-window layers). With fewer key/value heads
    than query heads (KV divides H) each key/value head serves H/KV query
    heads, and the scores are accumulated in float32. With equal head counts
    and no window the traced program is what it was before either existed.

    ``q_rope`` [B, T, H, R] and ``k_rope`` [B, T, 1, R] add a second term to
    every head's score against the one key all heads share (latent
    attention's rotary part), the sum taken in float32; ``scale`` multiplies
    the scores (default ``D ** -0.5``)."""
    D = q.shape[-1]
    H, KV = q.shape[2], k.shape[2]
    if q_rope is not None:
        return _two_term_attention(q, k, v, q_rope, k_rope, causal, window, scale)
    if scale is not None:
        raise ValueError("scale is given with the second score term only")
    if H != KV:
        return _grouped_query_attention(q, k, v, causal, window)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(D, jnp.float32)
    )
    if causal or window is not None:
        s = jnp.where(_attention_mask(q.shape[1], causal, window)[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)


def _attention_mask(T: int, causal: bool, window: Optional[int]):
    """[T, T] bool, query position by key position."""
    mask = jnp.tril(jnp.ones((T, T), bool)) if causal else jnp.ones((T, T), bool)
    if window is not None:
        i = jax.lax.iota(jnp.int32, T)
        mask = mask & (i[:, None] - i[None, :] < window)
    return mask


def _two_term_attention(q, k, v, q_rope, k_rope, causal, window, scale):
    """Scores ``(q . k + q_rope . k_rope) * scale`` in float32, one key head
    a query head and one ``k_rope`` for all; values of any width."""
    B, T, H, D = q.shape
    if k.shape[:3] != (B, T, H) or k_rope.shape[:3] != (B, T, 1):
        raise ValueError(
            f"a second score term needs a key head a query head and one k_rope: "
            f"q {q.shape}, k {k.shape}, k_rope {k_rope.shape}")
    if scale is None:
        scale = 1.0 / (D + q_rope.shape[-1]) ** 0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s + jnp.einsum(
        "bqhr,bkr->bhqk", q_rope, k_rope[:, :, 0], preferred_element_type=jnp.float32)
    s = s * scale
    if causal or window is not None:
        s = jnp.where(_attention_mask(T, causal, window)[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)


def _grouped_query_attention(q, k, v, causal: bool, window: Optional[int]):
    B, T, H, D = q.shape
    KV = k.shape[2]
    if H % KV:
        raise ValueError(f"{H} query heads do not divide over {KV} key/value heads")
    qg = q.reshape(B, T, KV, H // KV, D)
    s = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.asarray(D, jnp.float32))
    if causal or window is not None:
        s = jnp.where(_attention_mask(T, causal, window)[None, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v).reshape(B, T, H, v.shape[-1]).astype(q.dtype)
