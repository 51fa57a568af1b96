"""Rank-selection Pallas kernel for the Byzantine-robust aggregators.

Coordinate-wise median and trimmed mean (robustness/robust_aggregation.py)
reduce a ``[C, D]`` stack of client updates along the SMALL client axis
(C = cohort, typically 4–64) independently per coordinate (D = flattened
model, easily millions). XLA lowers ``jnp.median``/``jnp.sort`` to a full
variadic sort along the client axis — a comparator network materialized
per coordinate with its permutation bookkeeping, all streamed through HBM.

But nothing here needs a sort: per coordinate we only need *which* values
survive the trim window, and the rank of a value in a C-element column is
one broadcast comparison count. This kernel streams ``[C, block_d]``
tiles HBM→VMEM and computes, per lane (coordinate):

    rank_i = #{j : x_j < x_i}  +  #{j < i : x_j == x_i}      (stable rank)
    keep_i = trim_k <= rank_i < C - trim_k
    out    = sum(keep_i ? x_i : 0) / (C - 2*trim_k)

an O(C²) unrolled compare-accumulate on the VPU with no permutation
traffic, no scratch, and one pass over the data. The stable tie-break
(index order among equals) selects exactly the multiset a stable sort's
``s[k : C-k]`` window keeps, so the result matches the sort-based
reference up to fp32 summation order (exactly, when kept values are
exact — pinned by tests/test_robust_stats.py).

Median is the same kernel at ``trim_k = (C-1)//2`` for odd C (keeps the
middle value) and ``trim_k = C//2 - 1`` for even C (keeps — and averages
— the two middle values), matching ``jnp.median``'s mean-of-middle-two.

Which path runs is decided once, from the backend (``use_kernel=None``):
the kernel on a TPU, XLA's sort lowering everywhere else (byte-identical
to the historical reference; tests drive the kernel explicitly through
interpret mode). The sort path is the off-TPU route, NOT a rescue: on a
TPU a kernel that fails to lower raises — nothing drops to interpret mode
or to the sort (chip_smoke.py asserts the ``tpu_custom_call`` is in the
aggregation program). Krum stays on XLA either way — its sort is over the
tiny ``[C, C]`` Gram matrix, never a bottleneck."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK_D = 512


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _trimmed_kernel(x_ref, o_ref, *, C: int, trim_k: int):
    x = x_ref[:]  # [C, Bd] fp32
    keep_n = C - 2 * trim_k
    acc = jnp.zeros((1, x.shape[1]), jnp.float32)
    for i in range(C):  # C is static and small — fully unrolled VPU ops
        xi = x[i : i + 1, :]  # [1, Bd]
        rank = jnp.sum((x < xi).astype(jnp.int32), axis=0, keepdims=True)
        if i > 0:
            rank = rank + jnp.sum(
                (x[:i, :] == xi).astype(jnp.int32), axis=0, keepdims=True
            )
        keep = jnp.logical_and(rank >= trim_k, rank < C - trim_k)
        acc = acc + jnp.where(keep, xi, 0.0)
    o_ref[:] = acc / float(keep_n)


@functools.partial(
    jax.jit, static_argnames=("trim_k", "block_d", "interpret")
)
def _trimmed_mean_2d(x, trim_k: int, block_d: int, interpret: bool):
    C, D = x.shape
    x = x.astype(jnp.float32)
    pad = (-D) % block_d
    if pad:
        # zero pad columns compute a garbage mean that is sliced off below
        x = jnp.pad(x, ((0, 0), (0, pad)))
    out = pl.pallas_call(
        functools.partial(_trimmed_kernel, C=C, trim_k=trim_k),
        out_shape=jax.ShapeDtypeStruct((1, D + pad), jnp.float32),
        grid=((D + pad) // block_d,),
        in_specs=[
            pl.BlockSpec(
                (C, block_d), lambda i: (0, i), memory_space=pltpu.VMEM
            )
        ],
        out_specs=pl.BlockSpec(
            (1, block_d), lambda i: (0, i), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(x)
    return out[0, :D]


def trimmed_mean_1d(
    x,
    trim_k: int,
    use_kernel: bool | None = None,
    interpret: bool | None = None,
):
    """Per-coordinate trimmed mean of ``x`` [C, D] over axis 0: drop the
    ``trim_k`` largest and smallest values per coordinate, average the
    rest. ``use_kernel=None`` auto-selects the Pallas kernel on TPU and
    the XLA sort path elsewhere."""
    C = x.shape[0]
    if trim_k < 0 or 2 * trim_k >= C:
        raise ValueError(
            f"need 0 <= trim_k < C/2; got trim_k={trim_k}, C={C}"
        )
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        s = jnp.sort(x.astype(jnp.float32), axis=0)
        return jnp.mean(s[trim_k : C - trim_k], axis=0)
    if interpret is None:
        interpret = _use_interpret()
    return _trimmed_mean_2d(
        x, trim_k, min(_BLOCK_D, max(128, x.shape[1])), interpret
    )


def median_trim_k(C: int) -> int:
    """The trim window that makes :func:`trimmed_mean_1d` compute the
    median: keep 1 middle value (odd C) or average the 2 middle values
    (even C) — exactly ``jnp.median``'s semantics."""
    return (C - 1) // 2 if C % 2 else C // 2 - 1


def median_1d(x, use_kernel: bool | None = None, interpret: bool | None = None):
    """Per-coordinate median of ``x`` [C, D] over axis 0."""
    C = x.shape[0]
    if C == 1:
        return x.astype(jnp.float32)[0]
    return trimmed_mean_1d(
        x, median_trim_k(C), use_kernel=use_kernel, interpret=interpret
    )
