"""The state-space scan of a Mamba-2 mixer (state-space duality), chunked.

Per head ``h`` (``H`` heads of ``P`` dims), with a state ``S`` [P, N] that
starts at zero, one decay ``A_h < 0`` and one skip ``D_h`` a head, and the
``B_t``, ``C_t`` [N] of group ``h // (H / G)`` (``G`` groups share them):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

``dt`` is the step after its softplus, ``x`` what the convolution hands on;
both stay with the caller, as the projections do.

The chunked form computes the same function without a pass over positions.
With ``a_t = dt_t A`` (a log decay, never positive), chunks of ``Q``
positions and ``cum_t`` the sum of ``a`` from the chunk's first position
through ``t``:

- ``ssd_chunk`` (inside a chunk): ``y_t += sum_{s <= t} exp(cum_t - cum_s)
  (C_t . B_s) dt_s x_s``: the scores ``C B^T`` [Q, Q] a group, times the
  decay matrix ``L`` [Q, Q] a head (the mask is in ``L``: the difference is
  ``-inf`` above the diagonal before the exponential, so nothing overflows),
  times ``dt x`` [Q, P]. And what the chunk leaves behind:
  ``state_c = sum_s exp(cum_last - cum_s) dt_s x_s B_s^T`` [P, N].
- ``ssd_state`` (across chunks): the state a chunk starts from,
  ``S_c = sum_{c' < c} exp(sum of a over the chunks between) state_c'``: one
  [chunks, chunks] matrix of decays a head times the chunk states, a product
  and no loop (32 chunks at T = 4 096; 0.26 MFLOP a token against the 3.1
  of the rest).
- ``ssd_out``: ``y_t += exp(cum_t) C_t . S_c`` and the skip ``D x_t``.

At H = 64, P = 64, G = 8, N = 128, Q = 128 that is about 3.4 MFLOP a token
forward in products of 128 x 128 and 128 x 64 blocks, beside elementwise
work over ``L`` (H x Q numbers a token) whose exponentials the vector unit
pays for: bound by bandwidth and by ``exp``, not by the array.

Between the passes NOTHING but the operands is kept: the function is a
``jax.checkpoint``. Its residuals are ``x`` [T, H, P], ``B`` and ``C`` [T, G,
N] in the operands' dtype and ``dt`` [T, H] in float32: 12.5 kB a token and
layer in bfloat16 (51 MB at T = 4 096), and ``A``, ``D``. The backward pass
runs the forward again and differentiates it as ordinary JAX code (every
part is a product or an elementwise pass, so the transpose is products
too). Kept instead, autodiff's residuals would be ``L`` and the masked
scores [chunks, H, Q, Q] in float32 (134 MB each a layer at T = 4 096), the
chunk states and their starts [chunks, H, P, N] float32 (67 MB each) and
the [T, H, P] float32 pieces of ``y``, near 0.6 GB a layer and local step
in flight at once, for 3.4 of a layer's 160 MFLOP a token forward; under
the client ``scan`` schedule the round program has no such room.

Precision: ``dt``, the log decays, their cumulative sums, every exponential,
the chunk states and the state each chunk starts from are float32
(:data:`DECAY_DTYPE`) whatever dtype the operands arrive in. The operands of
each product are in ``x``'s dtype (bfloat16 in the training cells; the
float32 factors are rounded to it once, where they enter a product) and
every product accumulates in float32. ``y`` is rounded to ``x``'s dtype once.

Pure ``jax.numpy``: any leading axes, any length (a length that is no whole
number of chunks is padded with steps of ``dt = 0``, which decay nothing and
add nothing; a length under the chunk is one chunk of that length), and it
batches and scans like any product, which is what the client ``vmap``, the
client ``scan`` and the local-step scan need."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# What the decays, their cumulative sums and the states are computed in. The
# limits of the cell that trains this operator are set so that bfloat16 here
# fails them (benchmarks/limits/nemotron-twotower-30b-a3b.silo2t4k-ssm.json).
DECAY_DTYPE = jnp.float32


def _chunked(x, dt, A, B, C, D, q: int):
    """x [b, T, H, P], dt [b, T, H], A and D [H], B and C [b, T, G, N], T a
    whole number of chunks of ``q``."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    nc, per = T // q, H // G
    wide, low = DECAY_DTYPE, x.dtype
    with jax.named_scope("ssd_chunk"):
        a = (dt.astype(wide) * A.astype(wide)).reshape(b, nc, q, H)
        cum = jnp.cumsum(a, axis=2)                              # [b, c, q, H]
        cum_h = jnp.moveaxis(cum, 2, 3)                          # [b, c, H, q]
        below = jnp.tril(jnp.ones((q, q), bool))
        L = jnp.exp(jnp.where(below, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf))
        xc = x.reshape(b, nc, q, G, per, P)
        Bc, Cc = B.reshape(b, nc, q, G, N), C.reshape(b, nc, q, G, N)
        dtx = dt.astype(jnp.float32).reshape(b, nc, q, G, per, 1) * xc   # dt_s x_s, float32
        scores = jnp.einsum("bcqgn,bcsgn->bcgqs", Cc, Bc, preferred_element_type=jnp.float32)
        masked = scores[:, :, :, None] * L.reshape(b, nc, G, per, q, q)
        y = jnp.einsum("bcgjqs,bcsgjp->bcqgjp", masked.astype(low), dtx.astype(low),
                       preferred_element_type=jnp.float32)
        # what each chunk adds to the state after its last position
        to_end = jnp.exp(cum[:, :, -1:, :] - cum).reshape(b, nc, q, G, per, 1)
        states = jnp.einsum("bcsgn,bcsgjp->bcgjpn", Bc, (dtx * to_end).astype(low),
                            preferred_element_type=jnp.float32)
    with jax.named_scope("ssd_state"):
        ends = jnp.cumsum(cum[:, :, -1, :], axis=1)              # [b, c, H]: through chunk c
        starts = ends - cum[:, :, -1, :]                         # up to chunk c's start
        before = jnp.tril(jnp.ones((nc, nc), bool), -1)
        between = jnp.exp(jnp.where(
            before[:, :, None], starts[:, :, None, :] - ends[:, None, :, :], -jnp.inf))
        # float32 operands at the highest precision: by default the chip would
        # round both to bfloat16, and the states are float32
        entering = jnp.einsum("bzch,bchpn->bzhpn", between.astype(wide),
                              states.reshape(b, nc, H, P, N).astype(wide),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    with jax.named_scope("ssd_out"):
        carried = jnp.einsum(
            "bcqgn,bcgjpn->bcqgjp", Cc, entering.reshape(b, nc, G, per, P, N).astype(low),
            preferred_element_type=jnp.float32)
        y = y + carried * jnp.exp(cum).reshape(b, nc, q, G, per, 1)
        y = y + D.astype(jnp.float32).reshape(G, per, 1) * xc
        return y.reshape(b, T, H, P).astype(low)


def ssd(x, dt, A, B, C, D, chunk: int = 128):
    """x [..., T, H, P], dt [..., T, H] (after its softplus), A [H] (negative),
    B and C [..., T, G, N] with G dividing H, D [H] -> y [..., T, H, P] in x's
    dtype; the equations at the top of this file."""
    *lead, T, H, P = x.shape
    G, N = B.shape[-2:]
    if H % G:
        raise ValueError(f"ssd: {G} groups do not divide {H} heads")
    if dt.shape != (*lead, T, H) or B.shape != (*lead, T, G, N) or C.shape != B.shape:
        raise ValueError(
            f"ssd: x {x.shape} takes dt {(*lead, T, H)} and B, C [..., {T}, G, N], "
            f"got {dt.shape}, {B.shape}, {C.shape}")
    q = min(int(chunk), T)
    pad = -T % q

    def flat(v):
        """The leading axes as one, and steps of ``dt = 0`` (zeros of every
        operand) up to a whole chunk."""
        v = v.reshape(-1, *v.shape[len(lead):])
        return jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))

    @jax.checkpoint
    def run(x, dt, A, B, C, D):
        y = _chunked(flat(x), flat(dt), A, flat(B.astype(x.dtype)), flat(C.astype(x.dtype)), D, q)
        return y[:, :T].reshape(*lead, T, H, P)

    return run(x, dt, A, B, C, D)
