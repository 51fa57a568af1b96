"""Blockwise (flash) attention as Pallas TPU kernels: the attention core of
the transformer models' training step (``ops/attention.py`` chooses between
it and the plain form by the shapes it is handed).

The FlashAttention recipe (public algorithm: Dao et al. 2022) cut to the
sequence lengths the models train at: scores and probabilities live in VMEM
only (the T×T matrix the plain form writes to HBM and reads back), forward
keeps the output and one log-sum-exp per query row, and ONE backward kernel
recomputes P chunk by chunk and gives dQ, dK and dV from that one
recomputation (in the transposed frame, scores [keys, queries], so that the
row statistics broadcast as they are stored and only dQ's product needs a
transpose, of dS).

Geometry. A program instance holds the whole sequence of one batch row and
one lane tile of heads (``MAX_LENGTH`` bounds it by VMEM), and walks it in
row chunks of ``CHUNK`` queries with everything static: for each row chunk
the key chunks the mask empties are not there at all, the run of key chunks
it leaves whole is ONE wide product with no mask arithmetic, and only the
chunks the mask cuts compute it. A row chunk sees all its keys in one
step, so the softmax is taken in one pass: no running maximum, no
rescaling. (On one v5e a grid over key blocks with the online softmax took
27–45 % longer at the same shapes: PERF.md section 6, PR 29.)

Layout. The kernels read ``[B, T, H·D]`` — the ``[B, T, H, D]`` the models
hold, reshaped for free — so nothing is transposed around the call. A
program instance takes the fewest query heads whose lanes make whole
128-lane tiles (one head of 128, two of 64): each head's scores come from a
product over the whole tile with the other heads' lanes zeroed, which is
what a contraction of 64 costs the 128-deep MXU anyway, and each head's
output lanes are selected from a product that is a whole tile wide.
Grouped-query attention maps a query tile to its K/V tile in the block
index (K and V are not repeated H/KV times) and dK/dV sum over the group
inside the kernel; where heads are narrower than a tile a K/V head is
repeated to fill the tile its query heads read (twice at D = 64).

Two-term scores (latent attention). With ``q_rope`` / ``k_rope`` a head's
score is the SUM of two products, ``q . k + q_rope . k_rope``, where the
second key is ONE head that every query head shares (the rotary key of MLA)
and the values keep the first product's width. The two kernels take the
second pair as two more operands, padded to whole lane tiles by the wrapper
(a contraction of 64 costs the 128-deep MXU a whole pass either way); the
shared key is not repeated per head in HBM, and its gradient sums over the
heads in an accumulator of the backward kernel, as dK/dV of a group do.

Masks. Causal and sliding-window (``i - j < window``) masks have ONE
definition (``_Cfg.valid``) for both kernels, and one list of live key
pieces per row chunk (``_Cfg.pieces``).

Precision. Scores, softmax statistics and every accumulator are float32;
matmul operands stay in the dtype they arrive in (bfloat16 in the training
cells, float32 in the tests): P and dS are rounded to it for their
products, as the plain form rounds P.

Interpret mode is the CPU TEST route only: ``interpret=None`` resolves
from the backend, once, to "compiled" on a TPU and "interpret" elsewhere.
There is no second path behind it — on a TPU a kernel that fails to lower
raises (chip_smoke.py asserts the ``tpu_custom_call`` is in the program).

Measured on one TPU v5e (PERF.md section 6, PR 29; forward + gradient,
bfloat16): 2.18 ms at ``gpt2-124m.silo4``'s step (4 clients x [4, 1024, 12
heads of 64]) against the plain form's 7.58 ms and 3.29 ms for the fastest
kernel JAX ships (splash attention, fused backward, with the transposes it
needs); 2.05 ms at ``mellum2-12b-a2.5b.silo2``'s window layer ([2, 2048,
32/4 heads of 128], window 1 024) against 11.24 ms and 2.75 ms."""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
LANES = 128
_STAT_ROWS = 8  # float32 sublanes of one tile: the statistics' block height
_VMEM_LIMIT = 96 * 1024 * 1024  # of a v5e core's 128 MiB; the default scope is 16 MiB

# Queries a row chunk, and the longest sequence a program instance holds (at
# 8 192 the forward kernel's chunks want 123 MB of VMEM). 256 ran 4–8 %
# faster than 512 and 21–25 % faster than 1 024 at both cells' shapes.
CHUNK = 256
MAX_LENGTH = 4096


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def heads_per_tile(H: int, D: int) -> int:
    """Query heads one program instance takes: the fewest whose ``D`` lanes
    make whole 128-lane tiles, or all ``H`` (the block is then the array's
    full width, which any width may be) where no divisor of ``H`` does."""
    return next(
        (hp for hp in range(1, H + 1) if H % hp == 0 and (hp * D) % LANES == 0), H
    )


@dataclasses.dataclass(frozen=True)
class _Cfg:
    """What the two kernels share, all static."""

    head_dim: int
    heads: int        # query heads a program instance takes (one tile's)
    group_tiles: int  # query tiles that read one K/V tile
    scale: float      # of the scores: head_dim ** -0.5 unless the caller gives one
    rope_tile: int    # lanes of the second score term's operands; 0: one term
    causal: bool
    window: Optional[int]
    chunk_q: int
    chunk_k: int
    length_q: int
    length_k: int
    interpret: bool

    def row_chunks(self):
        return range(0, self.length_q, self.chunk_q)

    def pieces(self, r0: int):
        """The keys that queries ``[r0, r0 + chunk_q)`` see, as
        ``(c0, c1, cut)`` column ranges: key chunks the mask empties are
        left out, a run of chunks it leaves whole is one piece, and a chunk
        it cuts is a piece of its own that needs the mask arithmetic."""
        r1, out = r0 + self.chunk_q - 1, []
        for c0 in range(0, self.length_k, self.chunk_k):
            c1 = c0 + self.chunk_k - 1
            live = whole = True
            if self.causal:
                live, whole = live and c0 <= r1, whole and c1 <= r0
            if self.window is not None:
                live, whole = live and r0 - c1 < self.window, whole and r1 - c0 < self.window
            if not live:
                continue
            if whole and out and not out[-1][2] and out[-1][1] == c0:
                out[-1] = (out[-1][0], c1 + 1, False)
            else:
                out.append((c0, c1 + 1, not whole))
        return out

    def valid(self, r0: int, c0: int, c1: int, q_axis: int):
        """bool of the (query, key) pairs the mask keeps, queries from
        ``r0`` and keys ``[c0, c1)`` — the ONE definition forward and
        backward share (a divergence here is the classic silent fwd/bwd
        gradient mismatch). ``q_axis`` is the axis the queries run along
        (0; 1 in the backward kernel's transposed frame)."""
        shape = (self.chunk_q, c1 - c0) if q_axis == 0 else (c1 - c0, self.chunk_q)
        # query position minus key position
        gap = (
            jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
            + (r0 - c0)
        )
        ok = gap >= 0 if self.causal else None
        if self.window is not None:
            inside = gap < self.window
            ok = inside if ok is None else ok & inside
        return ok

    def only(self, x, i):
        """``x`` [rows, tile] with every lane but head ``i``'s zeroed."""
        if self.heads == 1:
            return x
        return jnp.where(self._head_lanes(x.shape, i), x, jnp.zeros_like(x))

    def pick(self, i, new, old):
        """``new`` on head ``i``'s lanes and ``old`` on the others."""
        if self.heads == 1 or old is None:
            return new
        return jnp.where(self._head_lanes(new.shape, i), new, old)

    def _head_lanes(self, shape, i):
        return jax.lax.broadcasted_iota(jnp.int32, shape, 1) // self.head_dim == i

    def compiler_params(self, semantics):
        if self.interpret:
            return None
        return pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=_VMEM_LIMIT)


_NT = (((1,), (1,)), ((), ()))  # [m, c] x [n, c] -> [m, n]


def _nt(a, b):
    return jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _stat_rows(heads: int) -> int:
    return -(-heads // _STAT_ROWS) * _STAT_ROWS


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, cfg):
    """``rest``: the second score term's ``q_rope`` and ``k_rope`` where
    there is one (``cfg.rope_tile``), then the two outputs."""
    *rope, o_ref, lse_ref = rest
    for r0 in cfg.row_chunks():
        rows = slice(r0, r0 + cfg.chunk_q)
        q, pieces, out = q_ref[0, rows, :], cfg.pieces(r0), None
        keeps = [cfg.valid(r0, c0, c1, 0) if cut else None for c0, c1, cut in pieces]
        for i in range(cfg.heads):
            q_i = cfg.only(q, i)
            scores = []  # one [chunk, keys of the piece] per piece
            for (c0, c1, _), keep in zip(pieces, keeps):
                s = _nt(q_i, k_ref[0, c0:c1, :])
                if rope:
                    s = s + _nt(rope[0][0, rows, :], rope[1][0, c0:c1, :])
                s = s * cfg.scale
                scores.append(s if keep is None else jnp.where(keep, s, _NEG_INF))
            m = functools.reduce(
                jnp.maximum, [jnp.max(s, axis=1, keepdims=True) for s in scores])
            l, o = 0.0, 0.0
            for s, (c0, c1, _) in zip(scores, pieces):
                p = jnp.exp(s - m)
                l = l + jnp.sum(p, axis=1, keepdims=True)
                # [chunk, tile]; head i's lanes are its P·V
                o = o + _nn(p.astype(v_ref.dtype), v_ref[0, c0:c1, :])
            out = cfg.pick(i, o / l, out)
            # one row of the statistics' tile per head (TPU block shapes
            # need the 2nd-to-last dim divisible by 8)
            lse_ref[0, 0, i:i + 1, rows] = (m + jnp.log(l))[:, 0][None, :]
        o_ref[0, rows, :] = out.astype(o_ref.dtype)


# Both wrappers are jitted so that every layer of a model calls ONE traced and
# lowered kernel: traced in place, the unrolled kernels of GPT-2's 12 layers
# made the round program's first call 47–50 s on the v5e's host where this
# makes it 23 s, the plain form's 21 (PERF.md section 6, PR 29).
@functools.partial(jax.jit, static_argnames="cfg")
def _flash_forward(q, k, v, rope, cfg):
    B, T, width = q.shape
    tile = cfg.heads * cfg.head_dim
    rows = _stat_rows(cfg.heads)
    q_spec = pl.BlockSpec((1, T, tile), lambda b, h: (b, 0, h))
    kv_spec = pl.BlockSpec(
        (1, cfg.length_k, tile), lambda b, h: (b, 0, h // cfg.group_tiles))
    in_specs = [q_spec, kv_spec, kv_spec]
    if rope:
        # a head's own second query, and the one second key all heads share
        in_specs += [pl.BlockSpec((1, T, cfg.rope_tile), lambda b, h: (b, 0, h)),
                     pl.BlockSpec((1, cfg.length_k, cfg.rope_tile), lambda b, h: (b, 0, 0))]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg),
        grid=(B, width // tile),
        in_specs=in_specs,
        out_specs=[q_spec, pl.BlockSpec((1, 1, rows, T), lambda b, h: (b, h, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, width // tile, rows, T), jnp.float32),
        ],
        compiler_params=cfg.compiler_params(("parallel", "parallel")),
        interpret=cfg.interpret,
        name="attention_fwd",
    )(q, k, v, *rope)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_kernel(q_ref, k_ref, v_ref, *rest, cfg):
    """Grid (batch, K/V tile, query tile of its group): dQ is this query
    tile's own, dK and dV are summed over the group in the accumulators.
    With a second score term (``cfg.rope_tile``) ``rest`` opens with
    ``q_rope`` and ``k_rope`` and holds a third gradient pair: dQ_rope is
    the query tile's own, dK_rope is summed over every head of the batch
    row (both inner grid axes) in an accumulator of its own."""
    n = 2 if cfg.rope_tile else 0
    rope, (do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref) = rest[:n], rest[n:n + 6]
    drope, (dk_acc, dv_acc, *dkr_acc) = rest[n + 6:2 * n + 6], rest[2 * n + 6:]
    g = pl.program_id(2)

    @pl.when(g == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if rope:
        c = pl.program_id(1)

        @pl.when((c == 0) & (g == 0))
        def _init_rope():
            dkr_acc[0][:] = jnp.zeros_like(dkr_acc[0])

    def add(acc, cols, i, part):
        acc[cols, :] = cfg.pick(i, acc[cols, :] + part, acc[cols, :])

    for r0 in cfg.row_chunks():
        rows = slice(r0, r0 + cfg.chunk_q)
        q, do, dq, pieces = q_ref[0, rows, :], do_ref[0, rows, :], None, cfg.pieces(r0)
        keeps = [cfg.valid(r0, c0, c1, 1) if cut else None for c0, c1, cut in pieces]
        if rope:
            qr, dqr = rope[0][0, rows, :], 0.0
        for i in range(cfg.heads):
            # [1, chunk] rows: they broadcast down the [keys, chunk] scores
            lse, delta = lse_ref[0, 0, i:i + 1, rows], delta_ref[0, 0, i:i + 1, rows]
            dq_i = 0.0
            for (c0, c1, _), keep in zip(pieces, keeps):
                cols = slice(c0, c1)
                k, v = k_ref[0, cols, :], v_ref[0, cols, :]
                s = _nt(cfg.only(k, i), q)  # [keys, chunk]
                if rope:
                    kr = rope[1][0, cols, :]
                    s = s + _nt(kr, qr)
                s = s * cfg.scale
                if keep is not None:
                    s = jnp.where(keep, s, _NEG_INF)
                p = jnp.exp(s - lse)
                add(dv_acc, cols, i, _nn(p.astype(do.dtype), do))
                ds = p * (_nt(cfg.only(v, i), do) - delta)
                add(dk_acc, cols, i, _nn(ds.astype(q.dtype), q))
                dq_i = dq_i + _nn(ds.T.astype(k.dtype), k)
                if rope:
                    dkr_acc[0][cols, :] += _nn(ds.astype(qr.dtype), qr)
                    dqr = dqr + _nn(ds.T.astype(kr.dtype), kr)
            dq = cfg.pick(i, dq_i, dq)
        dq_ref[0, rows, :] = (dq * cfg.scale).astype(dq_ref.dtype)
        if rope:
            drope[0][0, rows, :] = (dqr * cfg.scale).astype(drope[0].dtype)

    @pl.when(g == cfg.group_tiles - 1)
    def _finish():
        dk_ref[0] = (dk_acc[:] * cfg.scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    if rope:
        @pl.when((c == pl.num_programs(1) - 1) & (g == cfg.group_tiles - 1))
        def _finish_rope():
            drope[1][0] = (dkr_acc[0][:] * cfg.scale).astype(drope[1].dtype)


@functools.partial(jax.jit, static_argnames="cfg")
def _flash_backward(q, k, v, rope, out, lse, do, cfg):
    B, T, width = q.shape
    tile = cfg.heads * cfg.head_dim
    rows, G = lse.shape[2], cfg.group_tiles
    # delta = rowsum(dO * O) per head, in the layout of lse: [B, tiles, rows, T]
    delta = jnp.sum(
        (do.astype(jnp.float32) * out.astype(jnp.float32)).reshape(
            B, T, width // tile, cfg.heads, cfg.head_dim),
        axis=-1,
    )
    delta = jnp.pad(
        jnp.transpose(delta, (0, 2, 3, 1)), ((0, 0), (0, 0), (0, rows - cfg.heads), (0, 0))
    )
    q_spec = pl.BlockSpec((1, T, tile), lambda b, c, g: (b, 0, c * G + g))
    kv_spec = pl.BlockSpec((1, cfg.length_k, tile), lambda b, c, g: (b, 0, c))
    stat_spec = pl.BlockSpec((1, 1, rows, T), lambda b, c, g: (b, c * G + g, 0, 0))
    in_specs, out_specs = [q_spec, kv_spec, kv_spec], [q_spec, kv_spec, kv_spec]
    out_shape = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (q, k, v)]
    scratch = [
        pltpu.VMEM((cfg.length_k, tile), jnp.float32),
        pltpu.VMEM((cfg.length_k, tile), jnp.float32),
    ]
    # dK and dV are summed over the group's query tiles
    semantics = ("parallel", "parallel", "arbitrary")
    if rope:
        qr_spec = pl.BlockSpec((1, T, cfg.rope_tile), lambda b, c, g: (b, 0, c * G + g))
        kr_spec = pl.BlockSpec((1, cfg.length_k, cfg.rope_tile), lambda b, c, g: (b, 0, 0))
        in_specs += [qr_spec, kr_spec]
        out_specs += [qr_spec, kr_spec]
        out_shape += [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in rope]
        scratch.append(pltpu.VMEM((cfg.length_k, cfg.rope_tile), jnp.float32))
        # dK_rope is summed over every head of a batch row
        semantics = ("parallel", "arbitrary", "arbitrary")
    dq, dk, dv, *drope = pl.pallas_call(
        functools.partial(_bwd_kernel, cfg=cfg),
        grid=(B, k.shape[2] // tile, G),
        in_specs=in_specs + [q_spec, stat_spec, stat_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=cfg.compiler_params(semantics),
        interpret=cfg.interpret,
        name="attention_bwd",
    )(q, k, v, *rope, do, lse, delta)
    return dq, dk, dv, tuple(drope)


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash(q, k, v, rope, cfg):
    return _flash_forward(q, k, v, rope, cfg)[0]


def _flash_fwd(q, k, v, rope, cfg):
    out, lse = _flash_forward(q, k, v, rope, cfg)
    return out, (q, k, v, rope, out, lse)


def _flash_bwd(cfg, res, do):
    q, k, v, rope, out, lse = res
    return _flash_backward(q, k, v, rope, out, lse, do, cfg)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_bthd(
    q,
    k,
    v,
    causal: bool = True,
    window: Optional[int] = None,
    chunk: int = CHUNK,
    interpret: Optional[bool] = None,
    q_rope=None,
    k_rope=None,
    scale: Optional[float] = None,
):
    """Blockwise attention in the framework's layout, the signature of
    ``parallel/ring_attention.full_attention``: q [B, T, H, D], k and v
    [B, Tk, KV, D] with KV dividing H; ``window`` keeps, beside the causal
    mask, only the keys with ``i - j < window``. Sequence lengths are whole
    numbers of ``chunk`` (or shorter than one: the chunk is then the
    sequence) and at most ``MAX_LENGTH``. Differentiable via the backward
    kernel.

    ``q_rope`` [B, T, H, R] and ``k_rope`` [B, Tk, 1, R] add a second term
    to every head's score, ``q_rope . k_rope`` against the ONE key that all
    heads share (MLA's rotary part; its gradient is the sum over the heads);
    it needs ``KV == H`` and heads of whole lane tiles. ``scale`` multiplies
    the scores (default ``D ** -0.5``; with a second term the caller gives
    ``(D + R) ** -0.5``)."""
    B, T, H, D = q.shape
    Tk, KV = k.shape[1], k.shape[2]
    if interpret is None:
        interpret = _use_interpret()
    if k.shape != v.shape or k.shape[0] != B or k.shape[3] != D or H % KV:
        raise ValueError(
            f"q {q.shape} needs k and v of one shape [B, Tk, KV, D] with KV "
            f"dividing its heads, got k {k.shape}, v {v.shape}"
        )
    chunk_q, chunk_k = min(chunk, T), min(chunk, Tk)
    if T % chunk_q or Tk % chunk_k or max(T, Tk) > MAX_LENGTH:
        raise ValueError(
            f"sequence lengths ({T}, {Tk}) must be multiples of the chunk "
            f"({chunk}) and at most {MAX_LENGTH}"
        )
    if (causal or window is not None) and T != Tk:
        raise ValueError("a causal or window mask requires matching Q/K lengths")
    heads = heads_per_tile(H, D)
    if KV != H and heads > 1:
        # heads narrower than a tile: a K/V head fills the tile that its
        # query heads read, so those have to be heads of its own group
        if (H // KV) % heads:
            raise ValueError(
                f"{H // KV} query heads a K/V head do not fill tiles of {heads} heads"
            )
        k, v = (jnp.repeat(a, heads, axis=2) for a in (k, v))
    rope, rope_tile = (), 0
    if q_rope is not None:
        R = q_rope.shape[-1]
        if (q_rope.shape != (B, T, H, R) or k_rope.shape != (B, Tk, 1, R)
                or KV != H or D % LANES):
            raise ValueError(
                f"a second score term needs q_rope [B, T, H, R], k_rope [B, Tk, 1, R] and "
                f"as many key heads as query heads, each of whole {LANES}-lane tiles: got "
                f"q {q.shape}, k {k.shape}, q_rope {q_rope.shape}, k_rope {k_rope.shape}"
            )
        # whole lane tiles, zeros beyond R: they add nothing to a product
        rope_tile = -(-R // LANES) * LANES
        pad = ((0, 0), (0, 0), (0, 0), (0, rope_tile - R))
        rope = (jnp.pad(q_rope, pad).reshape(B, T, H * rope_tile),
                jnp.pad(k_rope, pad).reshape(B, Tk, rope_tile))
    cfg = _Cfg(
        head_dim=D, heads=heads, group_tiles=H // k.shape[2],
        scale=1.0 / math.sqrt(D) if scale is None else float(scale), rope_tile=rope_tile,
        causal=bool(causal), window=None if window is None else int(window),
        chunk_q=chunk_q, chunk_k=chunk_k, length_q=T, length_k=Tk,
        interpret=bool(interpret),
    )
    out = _flash(
        q.reshape(B, T, H * D), k.reshape(B, Tk, -1), v.reshape(B, Tk, -1), rope, cfg
    )
    return out.reshape(B, T, H, D)


def flash_attention(q, k, v, causal: bool = True, **kw):
    """The same for heads laid out ahead of the sequence: q/k/v
    [..., S, d] with any leading batch/head dims, each a head of its own."""
    if q.shape[:-2] != k.shape[:-2] or k.shape != v.shape:
        raise ValueError(
            f"q/k/v leading (batch, heads) dims must match: q {q.shape}, "
            f"k {k.shape}, v {v.shape} (broadcast MQA/GQA heads first)"
        )
    one_head = lambda a: a.reshape((-1,) + a.shape[-2:])[:, :, None, :]
    out = flash_attention_bthd(one_head(q), one_head(k), one_head(v), causal=causal, **kw)
    return out.reshape(q.shape)
