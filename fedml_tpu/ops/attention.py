"""The attention core of the transformer models: one entry, two forms.

``attention`` has the signature of ``parallel/ring_attention.full_attention``
and computes the same thing. It takes the blockwise Pallas kernel
(``ops/flash_attention.py``: no T x T scores or probabilities in HBM, forward
or backward) when the shapes it is handed allow that, and the plain form
otherwise; ``takes_kernel`` is that decision, a pure function of the shapes
and of nothing else — no option, no model name, no backend (off the TPU the
kernel runs interpreted, which the tests use). The training shapes of both
language-model cells qualify (T = 1 024 and 2 048); their 64-token
evaluation documents and the small sequences of the CPU tests do not."""

from __future__ import annotations

from typing import Optional

from fedml_tpu.ops.flash_attention import (
    CHUNK,
    MAX_LENGTH,
    flash_attention_bthd,
    heads_per_tile,
)
from fedml_tpu.parallel.ring_attention import full_attention


def takes_kernel(T: int, H: int, KV: int, D: int) -> bool:
    """Whether self-attention over ``T`` positions with ``H`` query heads on
    ``KV`` key/value heads of ``D`` goes to the kernel: ``T`` is a whole
    number of the kernel's row chunks and no longer than it holds, ``KV``
    divides ``H``, and where heads are narrower than a lane tile the heads
    of one tile share a K/V head."""
    if T % CHUNK or T > MAX_LENGTH or H % KV:
        return False
    return H == KV or (H // KV) % heads_per_tile(H, D) == 0


def attention(q, k, v, causal: bool = False, window: Optional[int] = None):
    """q [B, T, H, D], k and v [B, T, KV, D] → [B, T, H, D]; ``window``
    keeps, beside the causal mask, only the keys with ``i - j < window``."""
    T, H, D = q.shape[1:]
    if k.shape[1] == T and takes_kernel(T, H, k.shape[2], D):
        return flash_attention_bthd(q, k, v, causal=causal, window=window)
    return full_attention(q, k, v, causal=causal, window=window)
