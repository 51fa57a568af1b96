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
client ``scan`` and the local-step scan need.

The kernels. Where :func:`takes_kernel` holds (chunks of 128, whole chunks,
the state and a group's heads in whole lane tiles), the same function goes
to two Pallas kernels of this file under a ``jax.custom_vjp``, and XLA
lowers none of the above. Both take one group's ``H / G`` heads a program
instance (``B`` and ``C`` read once for all of them) and walk its chunks in
order with the state in fast memory:

- ``ssd_fwd``: per chunk the scores ``C B^T`` once, per head ``L`` and the
  masked scores in registers and fast memory, ``y`` written once; then the
  carried state ``S <- exp(cum_last) S + (dt x exp(cum_last - cum))^T B``
  (float32 ``[H/G x P, N]``). The sequential carry stands in for
  ``ssd_state``'s ``[chunks, chunks]`` product: the same sums in another
  order.
- ``ssd_bwd``: a first sweep over the chunks recomputes the state each one
  starts from into fast memory (float32, ``chunks x H/G x P x N``: 8 MiB at
  T = 4 096), which reads ``x``, ``B`` and the decays again (10.75 kB a token)
  where a residual from the forward pass would write and read 32 kB a token
  and hold 67 MB a layer; a second walks the chunks in reverse with the
  state's gradient ``dS`` in fast memory and writes ``dx``, ``dB``, ``dC``
  (the group's heads summed inside the instance), the decays' gradient and
  ``dD``'s sums over positions.

The step and the in-chunk cumulative sums of ``a = dt A`` are computed
outside the kernels by XLA, as ``_chunked`` does, and handed in laid out
with the positions on the lanes (``[groups, 2 x H/G, T]``: 256 B a token
each); their gradients come back the same way and autodiff carries them
through the cumulative sum to ``dt`` and ``A``. Precision is the chunked
form's: every decay, cumulative sum, exponential, state and ``dS`` float32,
product operands in ``x``'s dtype, float32 sums, ``y`` rounded once. Off the
TPU the kernels run interpreted, which the tests use."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.flash_attention import LANES, _use_interpret

# What the decays, their cumulative sums and the states are computed in. The
# limits of the cell that trains this operator are set so that bfloat16 here
# fails them (benchmarks/limits/nemotron-twotower-30b-a3b.silo2t4k-ssm.json).
DECAY_DTYPE = jnp.float32
CHUNK = 128  # positions a kernel step takes: one lane tile of scores
_STATES_BUDGET = 32 * 1024 * 1024  # of the v5e's 128 MiB VMEM: ssd_bwd's chunk states


def takes_kernel(T: int, H: int, P: int, G: int, N: int, chunk: int) -> bool:
    """Whether a scan over ``T`` positions of ``H`` heads of ``P`` with ``G``
    groups of state ``N`` by chunks of ``chunk`` goes to the kernels: chunks
    of ``CHUNK``, a whole number of them, the state a whole number of lane
    tiles, a group's heads whole lane tiles and whole float32 row tiles each,
    their four per-head columns (step, cumulative sum, two decays) within one
    lane tile, and the chunk states of ``ssd_bwd`` within ``_STATES_BUDGET``.
    A shape-only decision: no option, no model name, no backend."""
    if H % G:
        return False
    per = H // G
    return (chunk == CHUNK and T % CHUNK == 0 and N % LANES == 0
            and (per * P) % LANES == 0 and P % 8 == 0 and 4 * per <= LANES
            and (T // CHUNK) * per * P * N * 4 <= _STATES_BUDGET)


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


# --- the kernels ---------------------------------------------------------------


def _dot(a, b, contract_a: int, contract_b: int):
    return jax.lax.dot_general(a, b, (((contract_a,), (contract_b,)), ((), ())),
                               preferred_element_type=jnp.float32)


class _Chunk:
    """One chunk of one group as a kernel step sees it: the per-head columns
    (positions on the sublanes) of the step ``dt``, the cumulative log decay
    ``cum``, ``e = exp(cum)`` (what the entering state has decayed by) and
    ``w = exp(cum_last - cum)`` (what a position's input decays by to the
    chunk's end), made with one transpose from the rows the kernel is handed;
    the rows of ``cum`` (positions on the lanes); ``g = exp(cum_last)``; and
    which heads' lanes each lane tile of the group's ``H/G x P`` holds."""

    def __init__(self, rows, per: int, P: int, N: int):
        q = rows.shape[1]
        self.per, self.P = per, P
        dt, cum = rows[:per], rows[per:]
        last = cum[:, q - 1:q]
        parts = [dt, cum, jnp.exp(cum), jnp.exp(last - cum)]
        if 4 * per < LANES:
            parts.append(jnp.zeros((LANES - 4 * per, q), rows.dtype))
        cols = jnp.concatenate(parts, axis=0).T                 # [q, LANES]
        self.columns = {name: [cols[:, i * per + j:i * per + j + 1] for j in range(per)]
                        for i, name in enumerate(("dt", "cum", "e", "w"))}
        self.cum_rows = [cum[j:j + 1] for j in range(per)]      # [1, q] each
        # a [1, 1] broadcast to [P, N] in one step is no layout the chip has
        g = jnp.exp(jnp.broadcast_to(last, (per, N)))
        self.g = [g[j:j + 1] for j in range(per)]               # [1, N] each
        self.below = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
                      >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        # per lane tile: (head, its lanes there as a mask or None for all, as a slice)
        self.tiles = []
        for k in range(per * P // LANES):
            first = k * LANES
            heads = []
            for j in range(first // P, (first + LANES - 1) // P + 1):
                lo, hi = max(j * P - first, 0), min((j + 1) * P - first, LANES)
                whole = (lo, hi) == (0, LANES)
                heads.append((j, None if whole else (lanes >= lo) & (lanes < hi), slice(lo, hi)))
            self.tiles.append(heads)

    def decay(self, j: int):
        """``L`` of head ``j``: ``exp(cum_t - cum_s)`` for ``s <= t``, else 0."""
        diff = self.columns["cum"][j] - self.cum_rows[j]
        return jnp.exp(jnp.where(self.below, diff, -jnp.inf))

    def spread(self, name: str, k: int):
        """The named column of each head over its lanes of tile ``k``: [q, LANES]."""
        out = None
        for j, mask, _ in self.tiles[k]:
            col = self.columns[name][j]
            out = (jnp.broadcast_to(col, (col.shape[0], LANES)) if out is None
                   else jnp.where(mask, col, out))
        return out

    def by_head(self, k: int, part):
        """``part(j)`` [q, LANES] on head ``j``'s lanes of tile ``k``."""
        out = None
        for j, mask, _ in self.tiles[k]:
            out = part(j) if out is None else jnp.where(mask, part(j), out)
        return out

    def head_rows(self, j: int) -> slice:
        """Head ``j``'s rows of a ``[H/G x P, N]`` state."""
        return slice(j * self.P, (j + 1) * self.P)


def _head_sums(v, k: int, f: _Chunk):
    """Each head's sum over its lanes of tile ``k`` of ``v`` [q, LANES], as a
    row [1, q]: one transpose, then sums over row tiles (a sum across lanes
    would reduce every row tile by rotations)."""
    vt = v.T
    return {j: jnp.sum(vt[lanes], axis=0, keepdims=True) for j, _, lanes in f.tiles[k]}


def _advance(f: _Chunk, x_ref, B, S, s_ref, low):
    """``s_ref <- exp(cum_last) S + (dt x w)^T B``: the state after the chunk."""
    carried = []
    for k in range(len(f.tiles)):
        lanes = slice(k * LANES, (k + 1) * LANES)
        u = f.spread("dt", k) * x_ref[:, lanes].astype(jnp.float32)
        carried.append((u * f.spread("w", k)).astype(low))
    added = _dot(jnp.concatenate(carried, axis=1), B, 0, 0)     # [per P, N]
    for j in range(f.per):
        rows = f.head_rows(j)
        s_ref[rows, :] = f.g[j] * S[rows] + added[rows]


def _fwd_kernel(x_ref, rows_ref, b_ref, c_ref, d_ref, y_ref, s_ref, *, per, P):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    low = x_ref.dtype
    f = _Chunk(rows_ref[...], per, P, b_ref.shape[1])
    B, C, S = b_ref[...], c_ref[...], s_ref[...]
    scores = _dot(C, B, 1, 1)                                    # [q, q] a group
    carried = _dot(C, S.astype(low), 1, 1)                       # [q, per P]: C S^T a head
    mixed = [(scores * f.decay(j)).astype(low) for j in range(per)]
    for k in range(len(f.tiles)):
        lanes = slice(k * LANES, (k + 1) * LANES)
        x = x_ref[:, lanes].astype(jnp.float32)
        u = (f.spread("dt", k) * x).astype(low)
        y = f.by_head(k, lambda j: _dot(mixed[j], u, 1, 0))
        y = y + f.spread("e", k) * carried[:, lanes] + d_ref[:, lanes] * x
        y_ref[:, lanes] = y.astype(y_ref.dtype)
    _advance(f, x_ref, B, S, s_ref, low)


def _bwd_kernel(x_ref, rows_ref, b_ref, c_ref, d_ref, dy_ref,
                dx_ref, drows_ref, db_ref, dc_ref, dd_ref,
                s_ref, starts_ref, ds_ref, *, per, P, nc):
    step = pl.program_id(2)
    low = x_ref.dtype
    f = _Chunk(rows_ref[...], per, P, b_ref.shape[1])
    B = b_ref[...]

    @pl.when(step == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    @pl.when(step < nc)
    def _():  # first sweep: the state each chunk starts from
        S = s_ref[...]
        starts_ref[step] = S
        _advance(f, x_ref, B, S, s_ref, low)

    @pl.when(step >= nc)
    def _():  # second sweep, chunks in reverse
        S = starts_ref[2 * nc - 1 - step]
        dS_next = ds_ref[...]                 # the gradient of the state after the chunk
        C, S_low, dS_low = c_ref[...], S.astype(low), dS_next.astype(low)
        scores = _dot(C, B, 1, 1)
        carried = _dot(C, S_low, 1, 1)                           # [q, per P]
        d_added = _dot(B, dS_low, 1, 1)                          # [q, per P]
        decays = [f.decay(j) for j in range(per)]
        mixed = [scores * L for L in decays]
        mixed_low = [m.astype(low) for m in mixed]
        d_mixed = [None] * per
        d_dt = [0.0] * per                    # rows [1, q]: the positions on the lanes
        d_cum = [0.0] * per
        d_last = [0.0] * per                  # [1, 1]: into d_cum at the chunk's last position
        d_carried, added = [], []
        for k in range(len(f.tiles)):
            lanes = slice(k * LANES, (k + 1) * LANES)
            x = x_ref[:, lanes].astype(jnp.float32)
            dy = dy_ref[:, lanes].astype(jnp.float32)
            dy_low = dy.astype(low)
            dt, w = f.spread("dt", k), f.spread("w", k)
            u = dt * x
            u_low = u.astype(low)
            dz = f.spread("e", k) * dy
            d_carried.append(dz.astype(low))
            added.append((u * w).astype(low))
            through_w = d_added[:, lanes] * u * w    # d cum_s through exp(cum_last - cum_s)
            # d cum_t through exp(cum_t), less the above
            through_ew = _head_sums(dz * carried[:, lanes] - through_w, k, f)
            through_w = _head_sums(through_w, k, f)
            du = w * d_added[:, lanes] + f.by_head(k, lambda j: _dot(mixed_low[j], dy_low, 0, 0))
            through_dt = _head_sums(du * x, k, f)
            for j, mask, _ in f.tiles[k]:
                dy_j = dy_low if mask is None else jnp.where(mask, dy, 0.0).astype(low)
                part = _dot(dy_j, u_low, 1, 1)                   # [q, q]
                d_mixed[j] = part if d_mixed[j] is None else d_mixed[j] + part
                d_cum[j] = d_cum[j] + through_ew[j]
                d_last[j] = d_last[j] + jnp.sum(through_w[j], axis=1, keepdims=True)
                d_dt[j] = d_dt[j] + through_dt[j]
            dx_ref[:, lanes] = (d_ref[:, lanes] * dy + dt * du).astype(dx_ref.dtype)
            dd_ref[:, lanes] += jnp.sum(dy * x, axis=0, keepdims=True)
        d_scores = sum(dm * L for dm, L in zip(d_mixed, decays))
        for j in range(per):
            # d exp(cum_t - cum_s), times it: + its sum over s at t, - over t at s
            through_L = d_mixed[j] * mixed[j]
            d_cum[j] = d_cum[j] + (jnp.sum(through_L.T, axis=0, keepdims=True)
                                   - jnp.sum(through_L, axis=0, keepdims=True))
        d_scores_low = d_scores.astype(low)
        d_carried = jnp.concatenate(d_carried, axis=1)
        added = jnp.concatenate(added, axis=1)
        dc_ref[...] = (_dot(d_scores_low, B, 1, 0)
                       + _dot(d_carried, S_low, 1, 0)).astype(dc_ref.dtype)
        db_ref[...] = (_dot(d_scores_low, C, 0, 0)
                       + _dot(added, dS_low, 1, 0)).astype(db_ref.dtype)
        d_entering = _dot(d_carried, C, 0, 0)                    # [per P, N]
        for j in range(per):
            rows = f.head_rows(j)
            ds_ref[rows, :] = d_entering[rows] + f.g[j] * dS_next[rows]
            through_g = jnp.sum(jnp.sum(dS_next[rows] * S[rows], axis=1, keepdims=True),
                                axis=0, keepdims=True)
            d_last[j] = d_last[j] + f.g[j][:, :1] * through_g
        # the rows: dt's gradient, then cum's
        q = dy_ref.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (2 * per, q), 0)
        at_last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
        out = jnp.zeros((2 * per, q), jnp.float32)
        for j in range(per):
            last = jnp.where(at_last, jnp.broadcast_to(d_last[j], (1, q)), 0.0)
            out = jnp.where(row == j, d_dt[j], out)
            out = jnp.where(row == per + j, d_cum[j] + last, out)
        drows_ref[...] = out


def _specs(nc: int, per: int, P: int, N: int, backward: bool):
    """Block specs over the grid (lead, group, step): the chunk a step reads
    its forward operands at, and the one it reads ``C``, ``dy`` and writes
    at. Forward both are the step; backward the first sweep reads chunk
    ``step`` and holds the second's first chunk, the second reads and writes
    chunk ``2 nc - 1 - step``."""
    if backward:
        ahead = lambda c: jnp.where(c < nc, c, 2 * nc - 1 - c)
        behind = lambda c: jnp.minimum(nc - 1, 2 * nc - 1 - c)
    else:
        ahead = behind = lambda c: c
    q = CHUNK
    wide = lambda at: pl.BlockSpec((None, q, per * P), lambda i, g, c: (i, at(c), g))
    group = lambda at: pl.BlockSpec((None, q, N), lambda i, g, c: (i, at(c), g))
    rows = lambda at: pl.BlockSpec((None, None, 2 * per, q), lambda i, g, c: (i, g, 0, at(c)))
    skip = pl.BlockSpec((None, 1, per * P), lambda i, g, c: (g, 0, 0))   # D over a group's lanes
    return wide, group, rows, skip, ahead, behind


def _params(extra_vmem: int):
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                vmem_limit_bytes=extra_vmem + (32 << 20))


@functools.partial(jax.jit, static_argnames=("per", "P", "interpret"))
def _forward(x, rows, B, C, Dl, per, P, interpret):
    b, T, _ = x.shape
    G, N = rows.shape[1], B.shape[-1] // rows.shape[1]
    nc = T // CHUNK
    wide, group, row_spec, d_spec, at, _ = _specs(nc, per, P, N, backward=False)
    item = x.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_fwd_kernel, per=per, P=P),
        grid=(b, G, nc),
        in_specs=[wide(at), row_spec(at), group(at), group(at), d_spec],
        out_specs=wide(at),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((per * P, N), jnp.float32)],
        compiler_params=_params(0),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * T * G * (CHUNK * N + per * CHUNK * P + 2 * per * P * N),
            transcendentals=b * T * G * per * CHUNK,
            bytes_accessed=(2 * x.size + B.size + C.size) * item + rows.size * 4),
        interpret=interpret,
        name="ssd_fwd",
    )(x, rows, B, C, Dl)


@functools.partial(jax.jit, static_argnames=("per", "P", "interpret"))
def _backward(x, rows, B, C, Dl, dy, per, P, interpret):
    b, T, _ = x.shape
    G, N = rows.shape[1], B.shape[-1] // rows.shape[1]
    nc = T // CHUNK
    wide, group, row_spec, d_spec, ahead, behind = _specs(nc, per, P, N, backward=True)
    dd_spec = pl.BlockSpec((None, None, 1, per * P), lambda i, g, c: (i, g, 0, 0))
    item = x.dtype.itemsize
    states = nc * per * P * N * 4
    return pl.pallas_call(
        functools.partial(_bwd_kernel, per=per, P=P, nc=nc),
        grid=(b, G, 2 * nc),
        in_specs=[wide(ahead), row_spec(ahead), group(ahead), group(behind), d_spec,
                  wide(behind)],
        out_specs=[wide(behind), row_spec(behind), group(behind), group(behind), dd_spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(rows.shape, jnp.float32),
                   jax.ShapeDtypeStruct(B.shape, B.dtype),
                   jax.ShapeDtypeStruct(C.shape, C.dtype),
                   jax.ShapeDtypeStruct((b, G, 1, per * P), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((per * P, N), jnp.float32),
                        pltpu.VMEM((nc, per * P, N), jnp.float32),
                        pltpu.VMEM((per * P, N), jnp.float32)],
        compiler_params=_params(states),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * T * G * (2 * CHUNK * N + 2 * per * CHUNK * P + 7 * per * P * N),
            transcendentals=b * T * G * per * CHUNK,
            bytes_accessed=(5 * x.size + 3 * B.size + 2 * C.size) * item + 3 * rows.size * 4),
        interpret=interpret,
        name="ssd_bwd",
    )(x, rows, B, C, Dl, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(x, rows, B, C, Dl, per, P):
    return _forward(x, rows, B, C, Dl, per, P, _use_interpret())


def _scan_fwd(x, rows, B, C, Dl, per, P):
    return _forward(x, rows, B, C, Dl, per, P, _use_interpret()), (x, rows, B, C, Dl)


def _scan_bwd(per, P, residuals, dy):
    dx, drows, dB, dC, dD = _backward(*residuals, dy, per, P, _use_interpret())
    return dx, drows, dB, dC, jnp.sum(dD, axis=0)


_scan.defvjp(_scan_fwd, _scan_bwd)


def _kernels(x, dt, A, B, C, D):
    """x [b, T, H, P], dt [b, T, H], A and D [H], B and C [b, T, G, N] in
    x's dtype, where ``takes_kernel`` holds."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    per = H // G
    wide = DECAY_DTYPE
    dt = dt.astype(wide)
    cum = jnp.cumsum((dt * A.astype(wide)).reshape(b, T // CHUNK, CHUNK, H), axis=2)
    # [b, G, 2 per, T]: a group's steps, then its cumulative sums, positions on the lanes
    rows = jnp.stack([dt, cum.reshape(b, T, H)], axis=2).reshape(b, T, 2, G, per)
    rows = rows.transpose(0, 3, 2, 4, 1).reshape(b, G, 2 * per, T)
    Dl = jnp.repeat(D.astype(wide), P).reshape(G, 1, per * P)
    y = _scan(x.reshape(b, T, H * P), rows, B.reshape(b, T, G * N), C.reshape(b, T, G * N),
              Dl, per, P)
    return y.reshape(b, T, H, P)


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
    if takes_kernel(T, H, P, G, N, chunk):
        y = _kernels(x.reshape(-1, T, H, P), dt.reshape(-1, T, H), A,
                     B.astype(x.dtype).reshape(-1, T, G, N), C.astype(x.dtype).reshape(-1, T, G, N), D)
        return y.reshape(*lead, T, H, P)
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
