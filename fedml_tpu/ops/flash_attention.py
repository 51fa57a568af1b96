"""Blockwise (flash) attention as Pallas TPU kernels.

Standard FlashAttention blocking (public algorithm: Dao et al. 2022; online
softmax per Milakov & Gionis) written for the TPU memory hierarchy: Q/K/V
blocks stream HBM→VMEM via the grid's BlockSpecs, scores/probabilities never
materialise in HBM (the S×S matrix XLA would allocate), and every matmul is
MXU-shaped. Forward saves the log-sum-exp rows; backward recomputes P
blockwise and accumulates dQ/dK/dV in two passes (dQ over K blocks; dK/dV
over Q blocks).

The reference has no attention op at all (its NLP models are LSTMs,
rnn.py:5-38); this kernel exists for the framework's long-context leg —
it is the per-shard compute core under sequence-parallel ring attention
(parallel/ring_attention.py) and the transformer LM (models/transformer.py).

Interpret mode is the CPU TEST route only: ``interpret=None`` resolves
from the backend, once, to "compiled" on a TPU and "interpret" elsewhere.
There is no second path behind it — on a TPU a kernel that fails to lower
raises (chip_smoke.py asserts the ``tpu_custom_call`` is in the program).

Recorded before PR 8 on a v5e (bf16, causal, block 512; the shared chip
showed ~2× bimodal throughput windows so only interleaved A/B differences
were trusted) — NOT re-measured on today's code, see PERF.md:

- FORWARD-only, the kernel is at parity with XLA's attention lowering —
  XLA on TPU already avoids materialising the S×S scores (S=4096:
  ~11 ms both in the round-3 measurement, which used D=128; the training
  rows below use H=8 D=64, so the two sets of absolute numbers are not
  comparable to each other).
- The TRAINING step (fwd+bwd, H=8 D=64) is where the kernel wins:
  reverse-mode AD of plain jnp attention saves the S×S probabilities as
  a residual (H·S²·2 bytes — 2.1 GB at S=8192), while this kernel's
  custom VJP recomputes P blockwise. Interleaved best-of-5, twice
  reproduced: parity at S=4096, ~3× faster at S=8192 (116 vs 341 ms
  wall incl. ~100 ms of host fetch), ~1.35× at S=16384 (where XLA
  evidently switches to a rematerialising schedule itself).

Small blocks (≤256) are pathological (revisit overhead); keep ≥512 on
hardware."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _masked_scores(q, k, qi, ki, *, scale, causal, block_q, block_k):
    """Scaled scores for one (Q block, K block) pair with the causal mask —
    the ONE definition shared by forward and both backward kernels (a
    divergence here is the classic silent fwd/bwd gradient mismatch)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [Bq, Bk]
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(rows >= cols, s, _NEG_INF)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, block_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _step():
        s = _masked_scores(
            q_ref[0], k_ref[0], qi, ki, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        )
        m_prev = m_ref[:, :1]  # [Bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # [Bq, Bk]
        corr = jnp.exp(m_prev - m_new)  # [Bq, 1]
        l_ref[:, :1] = l_ref[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:, :1] = m_new
        pv = jnp.dot(
            p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
        )
        acc_ref[:] = acc_ref[:] * corr + pv

    if causal:
        # a block is live unless every (row, col) pair has col > row
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _():
            _step()
    else:
        _step()

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        # lse rides in a sublane-replicated [8, Bq] layout (TPU block
        # shapes need the 2nd-to-last dim divisible by 8)
        lse_row = (m_ref[:, :1] + jnp.log(safe_l))[:, 0]
        lse_ref[0] = jnp.broadcast_to(lse_row[None, :], (8, lse_row.shape[0]))


def _flash_forward(q, k, v, scale, causal, block_q, block_k, interpret):
    BH, S, d = q.shape
    Sk = k.shape[1]
    nq, nk = pl.cdiv(S, block_q), pl.cdiv(Sk, block_k)
    grid = (BH, nq, nk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, d), q.dtype),
            jax.ShapeDtypeStruct((BH, 8, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
            pltpu.VMEM((block_q, 128), jnp.float32),  # m (running max)
            pltpu.VMEM((block_q, 128), jnp.float32),  # l (running sum)
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v)
    return out, lse


def _compiler_params(interpret):
    """BH and Q-block grid dims are parallel; the K-block dim carries the
    online-softmax accumulator and must run in order."""
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale, causal, block_q, block_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def _step():
        k = k_ref[0]
        s = _masked_scores(
            q_ref[0], k, qi, ki, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        )
        p = jnp.exp(s - lse_ref[0, 0][:, None])  # [Bq, Bk]
        dov = jax.lax.dot_general(
            do_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )  # [Bq, Bk]
        ds = p * (dov - delta_ref[0, 0][:, None]) * scale
        acc_ref[:] += jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        )

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1)
        def _():
            _step()
    else:
        _step()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, block_q, block_k):
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _step():
        q = q_ref[0]
        s = _masked_scores(
            q, k_ref[0], qi, ki, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        )
        p = jnp.exp(s - lse_ref[0, 0][:, None])  # [Bq, Bk]
        do = do_ref[0].astype(jnp.float32)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Bk, d]
        dov = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        )
        ds = p * (dov - delta_ref[0, 0][:, None]) * scale  # [Bq, Bk]
        dk_acc[:] += jax.lax.dot_general(
            ds, q.astype(jnp.float32),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )  # [Bk, d]

    if causal:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            _step()
    else:
        _step()

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def _flash(q, k, v, causal, block_q, block_k, interpret):
    out, _ = _flash_forward(
        q, k, v, 1.0 / math.sqrt(q.shape[-1]), causal, block_q, block_k,
        interpret,
    )
    return out


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(
        q, k, v, 1.0 / math.sqrt(q.shape[-1]), causal, block_q, block_k,
        interpret,
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    BH, S, d = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    # delta in the same sublane-replicated [BH, 8, S] layout as lse
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )
    delta = jnp.broadcast_to(delta[:, None, :], (BH, 8, S))
    nq, nk = pl.cdiv(S, block_q), pl.cdiv(Sk, block_k)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b, 0, i)),
            pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
):
    """Blockwise attention: softmax(Q Kᵀ/√d [, causal]) V.

    q/k/v: [..., S, d] with any leading batch/head dims (flattened
    internally). Sequence lengths must be multiples of the block sizes
    (callers pad; ring attention's shards already are). Differentiable via
    the flash backward kernels."""
    if interpret is None:
        interpret = _use_interpret()
    orig_shape = q.shape
    S, d = q.shape[-2:]
    Sk = k.shape[-2]
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    if S % block_q or Sk % block_k:
        raise ValueError(
            f"sequence lengths ({S}, {Sk}) must be multiples of the block "
            f"sizes ({block_q}, {block_k})"
        )
    if causal and S != Sk:
        raise ValueError("causal attention requires matching Q/K lengths")
    q3 = q.reshape((-1, S, d))
    k3 = k.reshape((-1, Sk, d))
    v3 = v.reshape((-1, Sk, d))
    if q3.shape[0] != k3.shape[0] or k3.shape != v3.shape:
        # the grid is sized from Q's batch*heads; a smaller K/V (e.g. MQA
        # [B, 1, S, d]) would clamp block indices on TPU → silently wrong
        raise ValueError(
            f"q/k/v leading (batch, heads) dims must match: q {q.shape}, "
            f"k {k.shape}, v {v.shape} (broadcast MQA/GQA heads first)"
        )
    out = _flash(q3, k3, v3, causal, block_q, block_k, interpret)
    return out.reshape(orig_shape)


def flash_attention_bthd(q, k, v, causal: bool = True, **kw):
    """[B, T, H, D]-layout adapter matching the framework's attention
    callable convention (parallel/ring_attention.full_attention,
    models/transformer.TransformerBlock.attn_fn): drop-in flash-backed
    ``attn_fn`` for TransformerLM."""
    qt = jnp.swapaxes(q, 1, 2)  # [B, H, T, D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention(qt, kt, vt, causal=causal, **kw)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)
