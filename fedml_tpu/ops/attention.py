"""The attention core of the transformer models: one entry, two forms.

``attention`` has the signature of ``parallel/ring_attention.full_attention``
and computes the same thing. It takes the blockwise Pallas kernel
(``ops/flash_attention.py``: no T x T scores or probabilities in HBM, forward
or backward) when the shapes it is handed allow that, and the plain form
otherwise; ``takes_kernel`` is that decision, a pure function of the shapes
and of nothing else — no option, no model name, no backend (off the TPU the
kernel runs interpreted, which the tests use). The training shapes of all
four language-model cells qualify: T = 1 024, 2 048 and, since
``lfm2-8b-a1b.silo2t4k``, 4 096 = ``MAX_LENGTH``, the longest sequence the
kernel holds and now a trained length (32 query heads on 8 key/value heads
of 64: the two heads of a lane tile share a K/V head). Their 64-token
evaluation documents and the small sequences of the CPU tests do not.

Both forms also compute latent attention's two-term score: with ``q_rope``
and ``k_rope`` a head's score is ``(q . k + q_rope . k_rope) * scale``, the
second key being the one rotary key all heads share, and the values keep the
first product's width."""

from __future__ import annotations

from typing import Optional

from fedml_tpu.ops.flash_attention import (
    CHUNK,
    LANES,
    MAX_LENGTH,
    flash_attention_bthd,
    heads_per_tile,
)
from fedml_tpu.parallel.ring_attention import full_attention


def takes_kernel(T: int, H: int, KV: int, D: int, R: int = 0, V: Optional[int] = None) -> bool:
    """Whether self-attention over ``T`` positions with ``H`` query heads on
    ``KV`` key/value heads of ``D`` goes to the kernel: ``T`` is a whole
    number of the kernel's row chunks and no longer than it holds, ``KV``
    divides ``H``, and where heads are narrower than a lane tile the heads
    of one tile share a K/V head. A site of latent attention has two more
    widths, ``R`` of the second score term and ``V`` of the values: the
    kernel takes it where every head has keys of its own, in whole lane
    tiles, and values as wide as them (``DecoderLM.attention_sites`` holds
    the sites as the arguments after ``T``)."""
    if T % CHUNK or T > MAX_LENGTH or H % KV:
        return False
    if R:
        return H == KV and D % LANES == 0 and V == D
    return H == KV or (H // KV) % heads_per_tile(H, D) == 0


def attention(q, k, v, causal: bool = False, window: Optional[int] = None,
              q_rope=None, k_rope=None, scale: Optional[float] = None):
    """q [B, T, H, D], k and v [B, T, KV, D] → [B, T, H, D]; ``window``
    keeps, beside the causal mask, only the keys with ``i - j < window``.
    ``q_rope`` [B, T, H, R] and ``k_rope`` [B, T, 1, R] add the second score
    term; ``scale`` multiplies the scores (default ``D ** -0.5``)."""
    T, H, D = q.shape[1:]
    R = 0 if q_rope is None else q_rope.shape[-1]
    form = full_attention
    if k.shape[1] == T and takes_kernel(T, H, k.shape[2], D, R, v.shape[-1]):
        form = flash_attention_bthd
    return form(q, k, v, causal=causal, window=window,
                q_rope=q_rope, k_rope=k_rope, scale=scale)
