"""The selective scan of a Mamba-1 mixer: a diagonal recurrence whose decay
differs by channel AND by state element.

Per channel ``c`` (``C`` channels) and state element ``n`` (``N`` of them),
with a state ``h`` [N, C] that starts at zero:

    delta_t = softplus(dt_t + delta_bias)                    [C]
    h_t     = exp(delta_t A^T) * h_{t-1} + B_t (delta_t u_t)^T      [N, C]
    y_t     = C_t . h_t + D u_t                              [C]

``A`` [C, N] is negative (``-exp(A_log)``), ``B_t`` and ``C_t`` [N] are the
same for every channel, ``dt`` is the step before its softplus (the
``dt_proj`` product), ``u`` what the convolution hands on. The projections,
the convolution and the output gate stay with the caller.

Nothing factors the decay out of the state as ``ops/ssd.py`` does for
Mamba-2 (one decay a head): ``exp(delta_t A)`` is [C, N] at every position,
so the chunked products of state-space duality do not apply and the scan is
a pass over positions. Written plainly, its states are [T, C, N] float32:
1.34 GB a layer at T = 4 096, C = 5 120, N = 16, and autodiff of a scan keeps
them all.

Two forms, one function:

- The plain form (:func:`_sequential`): a ``lax.scan`` over positions with
  the state as its carry, in blocks of ``BLOCK`` positions whose inner scan
  is rematerialised, so that a gradient keeps one state a block and, for the
  block it is in, one a position. This is what the CPU tests and the
  64-token evaluation run.
- The kernels, where :func:`takes_kernel` holds (sequences of at least
  ``CHUNK`` positions, channels in whole lane tiles, the state in whole
  sublane tiles): two Pallas kernels under a ``jax.custom_vjp``.
  ``sscan_fwd`` holds a block of ``FWD_LANES`` channels' state [N, lanes]
  float32 in registers and walks the positions in order, ``GROUP`` at a
  time (their rows of ``u`` and ``dt`` loaded at once, each position's
  ``B_t`` and ``C_t`` a column of a small [N, GROUP] tile), writes ``y`` and,
  at each chunk's first position, the state it starts from (``N x C x 4``
  bytes a chunk: 5.2 MB a layer and sequence at T = 4 096). ``sscan_bwd``
  walks the chunks in reverse, ``BWD_LANES`` channels an instance, in three
  passes over a chunk: it recomputes the chunk's ``CHUNK + 1`` states from
  the one kept into fast memory and keeps each position's decay beside them
  (no exponential is taken twice), with dC summed once a group; then, a
  group at a time in reverse, it runs only the recurrence of the state's
  gradient at each position (``grad = ga + C_t dy_t``, stored;
  ``ga = grad * decay_t``, in registers), and after each group takes every
  other sum at once over the group's [GROUP, lanes] tiles: ``du``, ``ddt``,
  dB, and the group's share of the sums over positions of ``dA``, ``dD`` and
  ``d delta_bias``. dB and dC are summed over the channel blocks in their
  output block.

Precision: ``delta``, every decay and exponential, the states and ``dh``
are float32 (:data:`STATE_DTYPE`) whatever dtype the operands arrive in
(bfloat16 in the training cell); ``y``, ``du`` and ``ddt`` are rounded to
their operands' dtypes once. ``B`` and ``C`` enter the kernels in float32.
Every sum is float32, the lane sums of dB and dC too (the lane tiles added,
the stack of a group's sums turned on the transpose unit and its rows
added: no bfloat16 product stands in for them); the kernels add the terms in
another order than the recurrence, nothing else.

Off the TPU the kernels run interpreted, which the tests use."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedml_tpu.ops.flash_attention import LANES, _use_interpret

# What delta, the decays, the states and their gradient are computed in. The
# limits of the cell that trains this operator are set so that bfloat16 here
# fails them (benchmarks/limits/phi-4-mini-flash-reasoning.silo2t4k-sambay.json).
STATE_DTYPE = jnp.float32
CHUNK = 256       # positions a grid step of the kernels takes
GROUP = 16        # positions whose rows a kernel loads at once (a bfloat16 row tile)
FWD_LANES = 512   # channels a forward program instance holds the state of
BWD_LANES = 512   # channels a backward program instance holds the state of
BLOCK = 128       # positions in a rematerialised block of the plain form
_SUBLANES = 8


def takes_kernel(T: int, C: int, N: int) -> bool:
    """Whether a scan over ``T`` positions of ``C`` channels with a state of
    ``N`` goes to the kernels: at least one chunk of positions (a shorter
    length is padded to one whole chunk at the end, which no earlier
    position reads), channels in whole blocks of both kernels, and a state
    in whole float32 sublane tiles of at most a lane tile. A shape-only
    decision: no option, no model name, no backend."""
    return (T >= CHUNK and C % FWD_LANES == 0 and C % BWD_LANES == 0
            and N % _SUBLANES == 0 and N <= LANES)


def _softplus(x):
    """``log(1 + exp(x))`` in the form both the kernels and the plain form
    compute: no overflow for large ``x``, and ``log1p`` keeps the small steps
    of very negative ``x`` to their last bits."""
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


# --- the plain form ------------------------------------------------------------


def _sequential(u, dt, A, B, C, D, delta_bias):
    """u, dt [b, T, C], A [C, N], B and C [b, T, N], D and delta_bias [C]."""
    b, T, Ch = u.shape
    N = A.shape[1]
    wide = STATE_DTYPE
    A, D = A.astype(wide), D.astype(wide)
    delta = _softplus(dt.astype(wide) + delta_bias.astype(wide))

    def position(h, at):
        u_t, d_t, B_t, C_t = at                                     # [b, C], [b, C], [b, N] x2
        h = jnp.exp(d_t[:, :, None] * A) * h + (d_t * u_t)[:, :, None] * B_t[:, None, :]
        return h, jnp.sum(h * C_t[:, None, :], axis=-1) + D * u_t

    @jax.checkpoint
    def block(h, ats):
        return jax.lax.scan(position, h, ats)

    size = next(s for s in range(min(BLOCK, T), 0, -1) if T % s == 0)
    ats = [jnp.moveaxis(a.astype(wide), 1, 0).reshape(T // size, size, b, -1)
           for a in (u, delta, B, C)]
    _, y = jax.lax.scan(block, jnp.zeros((b, Ch, N), wide), ats)
    return jnp.moveaxis(y.reshape(T, b, Ch), 0, 1).astype(u.dtype)


# --- the kernels -----------------------------------------------------------------


def _rows(g):
    return pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)


def _pick(i, new, old):
    """Row ``i`` of ``old`` [GROUP, lanes] replaced by the row ``new``."""
    at = jax.lax.broadcasted_iota(jnp.int32, old.shape, 0) == i
    return jnp.where(at, new, old)


def _fwd_kernel(u_ref, dt_ref, b_ref, c_ref, at_ref, d_ref, bias_ref, y_ref, h0_ref, h_scr):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    h0_ref[...] = h_scr[...]
    AT, D, bias = at_ref[...], d_ref[...], bias_ref[...]

    def group(g, h):
        rows = _rows(g)
        u = u_ref[rows, :].astype(jnp.float32)                      # [GROUP, lanes]
        delta = _softplus(dt_ref[rows, :].astype(jnp.float32) + bias)
        du = delta * u
        Bg, Cg = b_ref[g], c_ref[g]                                 # [N, GROUP]
        y = D * u
        for i in range(GROUP):
            h = jnp.exp(delta[i:i + 1] * AT) * h + Bg[:, i:i + 1] * du[i:i + 1]
            y = _pick(i, y[i:i + 1] + jnp.sum(h * Cg[:, i:i + 1], axis=0, keepdims=True), y)
        y_ref[rows, :] = y.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, CHUNK // GROUP, group, h_scr[...])


def _put(ref, rows, v):
    """``v`` [rows, lanes] into a slab ``ref`` [lanes / 128, slab rows, 128] at
    ``rows``, a lane tile at a time (a strided store needs 128-lane rows)."""
    for c in range(ref.shape[0]):
        ref[c, rows, :] = v[:, c * LANES:(c + 1) * LANES]


def _get(ref, rows):
    """The rows ``rows`` of a slab ``ref``, lane tiles side by side again."""
    return jnp.concatenate([ref[c, rows, :] for c in range(ref.shape[0])], axis=1)


def _tiles(v):
    return [v[:, c * LANES:(c + 1) * LANES] for c in range(v.shape[1] // LANES)]


def _lane_sums(parts):
    """``parts``: N tiles [GROUP, lanes] -> [1, N x GROUP], the sum over lanes of
    row ``i`` of part ``n`` at lane ``n x GROUP + i``. Exact float32 sums in
    another order: the lane tiles added elementwise, the [N x GROUP, 128] stack
    turned on the transpose unit, and its rows added."""
    folded = [functools.reduce(jnp.add, _tiles(p)) for p in parts]
    return jnp.sum(jnp.concatenate(folded, axis=0).T, axis=0, keepdims=True)


def _bwd_kernel(u_ref, dt_ref, b_ref, c_ref, bn_ref, at_ref, d_ref, bias_ref, h0_ref, dy_ref,
                du_ref, ddt_ref, db_ref, dc_ref, dat_ref, dd_ref, dbias_ref,
                hs_scr, dec_scr, gr_scr, acc_scr, ga_scr, dat_scr, dd_scr, dbias_scr):
    """Grid (sequence, chunk in reverse, channel block): ``ga_scr``,
    ``dat_scr``, ``dd_scr`` and ``dbias_scr`` hold each channel block's
    carry and sums across the chunks; ``db_ref`` and ``dc_ref`` (a group's
    [N, GROUP] sums flattened on the lanes) are summed over the channel
    blocks, whose axis is the innermost. Three passes:

    1. the chunk's states again, in order: ``hs_scr`` holds the state each
       position starts from and the last one, ``dec_scr`` each position's
       decay, so that no exponential is taken twice; after each group, dC as
       one lane sum over the group's states times dy;
    2. a group's positions in reverse, with only the recurrence at each:
       ``grad = ga + C_t dy_t`` (dL/dh_t) into ``gr_scr``, ``ga = grad *
       decay_t``;
    3. the same group at once, a state element at a time as a [GROUP, lanes]
       tile: the sums over the state for du and d delta, the chunk's sums
       over positions for dA (``acc_scr``: eight rows an element, added over
       their sublanes at the chunk's end), and dB as one lane sum.

    The slabs hold a state element's positions in a run of rows (element
    ``n`` of position ``t`` at row ``n x stride + t``), so that pass 3 reads
    a tile of one element as contiguous rows, and the strides are odd, so
    that the strided store of one position's N elements puts its eight
    sublanes in eight different sublanes of memory. (With a position's
    elements in a run of rows instead, read an element at a time at a stride
    of 16 rows, the kernel took 1.5 times as long on a v5e.)

    Every sum is float32; only the order in which the terms are added differs
    from the recurrence."""
    k, j = pl.program_id(1), pl.program_id(2)
    groups = CHUNK // GROUP
    N = at_ref.shape[0]
    chunk_stride, group_stride = CHUNK + 1, GROUP + 1

    @pl.when(k == 0)
    def _():
        ga_scr[j] = jnp.zeros(ga_scr.shape[1:], ga_scr.dtype)
        dat_scr[j] = jnp.zeros(dat_scr.shape[1:], dat_scr.dtype)
        dd_scr[j] = jnp.zeros(dd_scr.shape[1:], dd_scr.dtype)
        dbias_scr[j] = jnp.zeros(dbias_scr.shape[1:], dbias_scr.dtype)

    @pl.when(j == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    acc_scr[...] = jnp.zeros_like(acc_scr)
    AT, D, bias = at_ref[...], d_ref[...], bias_ref[...]

    def at(t, stride):
        """The slab rows of position ``t``'s N elements."""
        return pl.ds(t, N, stride=stride)

    def run(t, n, stride):
        """The slab rows of element ``n`` at positions t .. t + GROUP - 1."""
        return pl.ds(n * stride + t, GROUP)

    h0 = h0_ref[...]
    _put(hs_scr, at(0, chunk_stride), h0)

    def forward(g, h):
        rows = _rows(g)
        u = u_ref[rows, :].astype(jnp.float32)
        delta = _softplus(dt_ref[rows, :].astype(jnp.float32) + bias)
        du = delta * u
        Bg = b_ref[g]
        for i in range(GROUP):
            t = g * GROUP + i
            decay = jnp.exp(delta[i:i + 1] * AT)
            _put(dec_scr, at(t, chunk_stride), decay)
            h = decay * h + Bg[:, i:i + 1] * du[i:i + 1]
            _put(hs_scr, at(t + 1, chunk_stride), h)
        dy = dy_ref[rows, :].astype(jnp.float32)
        dc_ref[pl.ds(g, 1), :] += _lane_sums(
            [_get(hs_scr, run(g * GROUP + 1, n, chunk_stride)) * dy for n in range(N)])
        return h

    jax.lax.fori_loop(0, groups, forward, h0)

    def backward(r, carry):
        ga, dD, dbias = carry
        g = groups - 1 - r
        rows = _rows(g)
        dy = dy_ref[rows, :].astype(jnp.float32)
        Cg = c_ref[g]
        for i in reversed(range(GROUP)):
            grad = ga + Cg[:, i:i + 1] * dy[i:i + 1]                # dL/dh_t
            _put(gr_scr, at(i, group_stride), grad)
            ga = grad * _get(dec_scr, at(g * GROUP + i, chunk_stride))

        u = u_ref[rows, :].astype(jnp.float32)
        x = dt_ref[rows, :].astype(jnp.float32) + bias
        delta = _softplus(x)
        du = delta * u
        Bn = bn_ref[rows, :]                                        # [GROUP, N]
        products = []
        for n in range(N):
            grad = _get(gr_scr, run(0, n, group_stride))            # [GROUP, lanes]
            # d(delta_t A^T) at element n, elementwise
            through_a = (grad * _get(hs_scr, run(g * GROUP, n, chunk_stride))
                         * _get(dec_scr, run(g * GROUP, n, chunk_stride)))
            tb, tat = Bn[:, n:n + 1] * grad, through_a * AT[n:n + 1]
            through_b = tb if n == 0 else through_b + tb
            through_at = tat if n == 0 else through_at + tat
            dat = through_a * delta
            for c, part in enumerate(_tiles(dat[:_SUBLANES] + dat[_SUBLANES:])):
                acc_scr[c, pl.ds(n * _SUBLANES, _SUBLANES), :] += part
            products.append(grad * du)
        d_x = (u * through_b + through_at) * jax.nn.sigmoid(x)
        du_ref[rows, :] = (D * dy + delta * through_b).astype(du_ref.dtype)
        ddt_ref[rows, :] = d_x.astype(ddt_ref.dtype)
        db_ref[pl.ds(g, 1), :] += _lane_sums(products)
        return (ga, dD + jnp.sum(dy * u, axis=0, keepdims=True),
                dbias + jnp.sum(d_x, axis=0, keepdims=True))

    ga, dD, dbias = jax.lax.fori_loop(0, groups, backward, (ga_scr[j], dd_scr[j], dbias_scr[j]))
    dAT = functools.reduce(jnp.add, [_get(acc_scr, pl.ds(s, N, stride=_SUBLANES))
                                     for s in range(_SUBLANES)], dat_scr[j])
    ga_scr[j], dat_scr[j], dd_scr[j], dbias_scr[j] = ga, dAT, dD, dbias
    dat_ref[...], dd_ref[...], dbias_ref[...] = dAT, dD, dbias


def _params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics, vmem_limit_bytes=64 << 20)


@functools.partial(jax.jit, static_argnames="interpret")
def _forward(u, dt, Bg, Cg, AT, D, bias, interpret):
    b, T, Ch = u.shape
    N, nc, lanes = AT.shape[0], T // CHUNK, FWD_LANES
    wide = pl.BlockSpec((None, CHUNK, lanes), lambda i, j, k: (i, k, j))
    grouped = pl.BlockSpec((None, CHUNK // GROUP, N, GROUP), lambda i, j, k: (i, k, 0, 0))
    chan = lambda rows: pl.BlockSpec((rows, lanes), lambda i, j, k: (0, j))
    item = u.dtype.itemsize
    return pl.pallas_call(
        _fwd_kernel,
        grid=(b, Ch // lanes, nc),
        in_specs=[wide, wide, grouped, grouped, chan(N), chan(1), chan(1)],
        out_specs=[wide, pl.BlockSpec((None, None, N, lanes), lambda i, j, k: (i, k, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((b, nc, N, Ch), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, lanes), jnp.float32)],
        compiler_params=None if interpret else _params(("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=8 * b * T * Ch * N, transcendentals=b * T * Ch * (N + 2),
            bytes_accessed=(3 * u.size) * item + (Bg.size + Cg.size) * 4 + nc * N * Ch * 4),
        interpret=interpret,
        name="sscan_fwd",
    )(u, dt, Bg, Cg, AT, D, bias)


@functools.partial(jax.jit, static_argnames="interpret")
def _backward(u, dt, Bg, Cg, AT, D, bias, h0, dy, interpret):
    b, T, Ch = u.shape
    N, nc, lanes = AT.shape[0], T // CHUNK, BWD_LANES
    blocks, tiles = Ch // lanes, lanes // LANES
    back = lambda k: nc - 1 - k
    wide = pl.BlockSpec((None, CHUNK, lanes), lambda i, k, j: (i, back(k), j))
    grouped = pl.BlockSpec((None, CHUNK // GROUP, N, GROUP), lambda i, k, j: (i, back(k), 0, 0))
    natural = pl.BlockSpec((None, CHUNK, N), lambda i, k, j: (i, back(k), 0))
    flat = pl.BlockSpec((None, CHUNK // GROUP, N * GROUP), lambda i, k, j: (i, back(k), 0))
    chan = lambda rows: pl.BlockSpec((rows, lanes), lambda i, k, j: (0, j))
    sums = lambda rows: pl.BlockSpec((None, rows, lanes), lambda i, k, j: (i, 0, j))
    state = pl.BlockSpec((None, None, N, lanes), lambda i, k, j: (i, back(k), 0, j))
    Bn = jnp.swapaxes(Bg, 2, 3).reshape(b, T, N)                   # B as the scan takes it
    item = u.dtype.itemsize
    du, ddt, dB, dC, dAT, dD, dbias = pl.pallas_call(
        _bwd_kernel,
        grid=(b, nc, blocks),
        in_specs=[wide, wide, grouped, grouped, natural, chan(N), chan(1), chan(1), state, wide],
        out_specs=[wide, wide, flat, flat, sums(N), sums(1), sums(1)],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct(dt.shape, dt.dtype),
                   jax.ShapeDtypeStruct((b, T // GROUP, N * GROUP), jnp.float32),
                   jax.ShapeDtypeStruct((b, T // GROUP, N * GROUP), jnp.float32),
                   jax.ShapeDtypeStruct((b, N, Ch), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, Ch), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, Ch), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tiles, N * (CHUNK + 1), LANES), jnp.float32),
                        pltpu.VMEM((tiles, N * (CHUNK + 1), LANES), jnp.float32),
                        pltpu.VMEM((tiles, N * (GROUP + 1), LANES), jnp.float32),
                        pltpu.VMEM((tiles, N * _SUBLANES, LANES), jnp.float32),
                        pltpu.VMEM((blocks, N, lanes), jnp.float32),
                        pltpu.VMEM((blocks, N, lanes), jnp.float32),
                        pltpu.VMEM((blocks, 1, lanes), jnp.float32),
                        pltpu.VMEM((blocks, 1, lanes), jnp.float32)],
        compiler_params=None if interpret else _params(("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=26 * b * T * Ch * N, transcendentals=b * T * Ch * (N + 5),
            bytes_accessed=(6 * u.size) * item + (3 * Bg.size + 2 * Cg.size) * 4 + nc * N * Ch * 4),
        interpret=interpret,
        name="sscan_bwd",
    )(u, dt, Bg, Cg, Bn, AT, D, bias, h0, dy)
    return du, ddt, dB.reshape(Bg.shape), dC.reshape(Cg.shape), dAT, dD, dbias


@jax.custom_vjp
def _scan(u, dt, Bg, Cg, AT, D, bias):
    return _forward(u, dt, Bg, Cg, AT, D, bias, _use_interpret())[0]


def _scan_fwd(u, dt, Bg, Cg, AT, D, bias):
    y, h0 = _forward(u, dt, Bg, Cg, AT, D, bias, _use_interpret())
    return y, (u, dt, Bg, Cg, AT, D, bias, h0)


def _scan_bwd(residuals, dy):
    du, ddt, dB, dC, dAT, dD, dbias = _backward(*residuals, dy, _use_interpret())
    return du, ddt, dB, dC, jnp.sum(dAT, axis=0), jnp.sum(dD, axis=0), jnp.sum(dbias, axis=0)


_scan.defvjp(_scan_fwd, _scan_bwd)


def _kernels(u, dt, A, B, C, D, delta_bias):
    """The operands laid out for the kernels: positions padded to whole
    chunks at the end (zeros, which no earlier position reads, and whose
    cotangents are zero), ``B`` and ``C`` as [T / GROUP, N, GROUP] float32
    tiles (a group's positions on the lanes), ``A`` as [N, C]."""
    b, T, Ch = u.shape
    N = A.shape[1]
    pad = -T % CHUNK
    Tp = T + pad

    def padded(v):
        return jnp.pad(v, ((0, 0), (0, pad), (0, 0)))

    def grouped(v):
        v = padded(v.astype(jnp.float32)).reshape(b, Tp // GROUP, GROUP, N)
        return jnp.swapaxes(v, 2, 3)

    wide = STATE_DTYPE
    y = _scan(padded(u), padded(dt), grouped(B), grouped(C), A.astype(wide).T,
              D.astype(wide).reshape(1, Ch), delta_bias.astype(wide).reshape(1, Ch))
    return y[:, :T]


def selective_scan(u, dt, A, B, C, D, delta_bias):
    """u and dt [..., T, C] (``dt`` before its softplus), A [C, N] (negative),
    B and C [..., T, N], D and delta_bias [C] -> y [..., T, C] in u's dtype;
    the equations at the top of this file."""
    *lead, T, Ch = u.shape
    N = A.shape[-1]
    if dt.shape != u.shape or A.shape != (Ch, N) or B.shape != (*lead, T, N) or C.shape != B.shape:
        raise ValueError(
            f"selective_scan: u {u.shape} takes dt of its shape, A [{Ch}, N] and B, C "
            f"[..., {T}, N], got {dt.shape}, {A.shape}, {B.shape}, {C.shape}")
    flat = [v.reshape(-1, *v.shape[len(lead):]) for v in (u, dt, B, C)]
    form = _kernels if takes_kernel(T, Ch, N) else _sequential
    y = form(flat[0], flat[1], A, flat[2], flat[3], D, delta_bias)
    return y.reshape(*lead, T, Ch)
