"""Spec-driven decoder-only language model: the block shapes of today's open
models, written from the keys of a Hugging Face ``config.json``.

    x0 = E[token]
    h  = x + Mixer(n)                            n = RMSNorm(x)
    y  = h + FFN(RMSNorm(h))
    logits = RMSNorm(y_L) W_head                 (W_head = E^T when tied)

or, where the spec has a ``hybrid_override_pattern`` (HF's ``nemotron_h``), a
stack of ONE part a layer under ONE norm:

    x  = x + Part_i(RMSNorm_i(x))                Part_i named by character i

- RMS norms (float32 statistics, learned scale), no biases, no learned
  positions.
- ``layer_types`` gives every layer's kind, which is its token mixer:
  ``full_attention`` (causal), ``sliding_attention`` (causal, and ``i - j <
  sliding_window``) or ``conv``; a spec without it has ``num_hidden_layers``
  full layers.
- A ``conv`` layer mixes tokens by a gated short convolution and no
  attention (HF's ``Lfm2MoeShortConv``; ``ops/short_conv.py``): ``[B, C, X] =
  n W_in`` (d -> 3d, split in that order), ``u = B * X``, a causal depthwise
  convolution of ``conv_L_cache`` taps a channel over ``u`` (zero before
  position 0, no bias), ``Mixer = (C * c) W_out``. No activation, no
  softmax, no positions; ``u`` and the sums over the taps are float32.
- Attention is of one of two kinds, both through the one attention core
  ``ops/attention.attention`` (the blockwise kernel at training lengths, the
  plain form at short ones):
  - grouped-query: ``num_attention_heads`` query heads of ``head_dim`` on
    ``num_key_value_heads`` key/value heads (the query width need not equal
    ``hidden_size``), or layer i's own ``num_attention_heads_per_layer[i]``
    on the same key/value heads (HF's ``laguna``); with ``use_qk_norm`` an
    RMS norm with a learned scale of ``head_dim`` over each head's dims of q
    and of k (one scale for all heads of q, one for k) ahead of rotary;
    rotate-half rotary on the first ``R = head_dim x partial_rotary_factor``
    dims of q and k (every dim by default; the others pass unchanged, HF's
    ``cat(rotated, pass)``), one table per layer kind from
    ``rope_parameters[kind]`` (``rope_type`` ``default`` or ``yarn``, as HF's
    ``_compute_yarn_parameters`` over the ``R`` rotated dims: blended
    frequencies, cos and sin scaled by ``attention_factor``, which so scales
    the rotated dims alone) or, without ``rope_parameters``, the default
    type at ``rope_theta`` over every dim of every layer;
  - latent (MLA, HF's ``DeepseekV3Attention``), where ``kv_lora_rank`` is
    given: ``q = n Wq`` holds per head ``qk_nope_head_dim`` dims without and
    ``qk_rope_head_dim`` with rotary (``q_lora_rank`` null: no query
    latent); ``n Wkva`` gives the ``kv_lora_rank`` latent and ONE rotary key
    for all heads; the RMS-normed latent times ``Wkvb`` gives every head's
    ``k_nope`` and its ``v`` of ``v_head_dim``; scores are ``(q_nope .
    k_nope + q_rope . k_rope) / sqrt(nope + rope)``. Rotary is the default
    type at ``rope_theta`` over the rope dims alone; with ``rope_interleave``
    the pairs are HF's ``(2m, 2m + 1)``, rotated where they lie: the
    de-interleaving HF applies to q and k alike is a permutation of the
    dims a dot product sums over, and cancels in every score.
- The first ``first_k_dense_replace`` layers' feed-forward is one gated-SiLU
  MLP of ``intermediate_size``; every other layer's is a routed expert layer
  (:func:`routed_experts`): a router over all experts (``num_experts`` or,
  as DeepSeek-style configs name it, ``n_routed_experts``), softmax or
  sigmoid scores (``scoring_func``), top-``num_experts_per_tok`` of the
  scores or, with ``topk_method`` ``noaux_tc``, of the scores plus a
  selection bias that enters the choice and not the weight, renormalised
  when ``norm_topk_prob`` by the chosen scores' sum plus ``renorm_eps``
  (by default HF's ``DeepseekV3`` 1e-20 under sigmoid scores and nothing
  under softmax; HF's ``lfm2_moe`` router adds 1e-6), times
  ``routed_scaling_factor``; gated-SiLU experts of width
  ``moe_intermediate_size``, no capacity and no dropped pair; and beside
  them, where ``n_shared_experts``, one gated-SiLU MLP of
  ``n_shared_experts x moe_intermediate_size`` that every token takes. A
  chip that holds a share of the experts moves only the rows its share is
  likely to own: the sorted (token, slot) rows are taken ``row_bound`` at a
  time (twice the even share, from shapes alone), and a step that routes
  more than that here takes them again.

A one-part stack (``hybrid_override_pattern``, a string over ``M``, ``E``,
``*`` and ``-``; the leaf of a layer's one norm is ``norm``):

- ``M``, a Mamba-2 state-space mixer (HF's ``NemotronHMamba2Mixer``;
  :meth:`DecoderLayer.mamba`): ``H = mamba_num_heads`` heads of ``P =
  mamba_head_dim`` (inner width ``H P``; HF's ``expand`` sets no shape),
  ``G = n_groups`` groups, state ``N = ssm_state_size``. ``[z | xBC | dt] = n
  W_in`` (d -> H P + (H P + 2 G N) + H, no bias); ``xBC <- SiLU(conv(xBC) +
  b)``, a causal depthwise convolution of ``conv_kernel`` taps a channel
  (``ops/short_conv.silu_short_conv``; ``use_conv_bias``); ``[x | B | C] =
  xBC``, head ``h`` reading the B, C of group ``h // (H / G)``; ``step =
  softplus(dt + dt_bias)`` with no clamp, ``A = -exp(A_log)``; per head, with
  a state ``S`` [P, N] from zero, ``S_t = exp(step_t A) S_{t-1} + step_t x_t
  B_t^T``, ``y_t = S_t C_t + D x_t`` (``ops/ssd.ssd``: the chunked scan,
  ``chunk_size`` positions a chunk, which changes no number); ``y <-
  GroupRMSNorm(y * SiLU(z)) * w`` over groups of ``H P / G``, the gate
  first (:func:`gated_group_norm`); ``Part = y W_out``. The step, the
  decays, their cumulative sums and the states are float32. The leaves
  ``A_log`` (``log`` of a uniform draw in [1, 16]), ``dt_bias`` (the inverse
  softplus of a step drawn log-uniformly in [``time_step_min``,
  ``time_step_max``], floored at ``time_step_floor``), ``D`` (ones), ``conv``
  (deviation ``1 / sqrt(taps)``) and ``conv_bias`` (zeros) are parameters
  like any other: FedAvg averages them as they are (``A_log`` as a
  logarithm).
- ``*``, grouped-query attention WITHOUT positions (``NemotronHAttention``):
  no rotary, no tables built, ``rope_sites`` empty; ``rope_theta`` is read
  by nothing. ``use_qk_norm`` still norms where a spec asks for it.
- ``E``, the routed expert layer above with UNGATED experts: ``relu(x
  W_up)**2 W_down``, two matrices an expert (``mlp_hidden_act`` ``relu2``),
  and one shared expert of the same form at
  ``moe_shared_expert_intermediate_size`` where ``n_shared_experts``.
- ``-``, a dense ``relu(x W_up)**2 W_down`` of ``intermediate_size`` alone.

A SambaY stack (``mb_per_layer``, HF's ``phi4flash``; arXiv:2507.06607):
``x = x + Mixer(LN(x))``, ``x = x + MLP(LN(x))`` with LayerNorms (scale and
bias, ``layer_norm_eps``) and a gated-SiLU MLP of ``intermediate_size`` in
every layer, no positions, and a tied head under a final LayerNorm. Of the
source's ``num_hidden_layers`` L the stack holds ``layers_held`` (a pipeline
stage), each layer's kind from its published index l
(:meth:`DecoderLM.sambay_layers`): a Mamba layer where ``l % mb_per_layer ==
0`` and differential attention (:meth:`DecoderLayer.differential`) elsewhere,
over a window before L / 2; at L / 2 the Mamba-1 mixer
(:meth:`DecoderLayer.mamba_one`, ``ops/selective_scan.py``; ``mamba_d_state``,
``mamba_d_conv``, ``mamba_expand``) keeps its scan output as
the memory and at L / 2 + 1 full attention keeps its keys and values; from
L / 2 + 2 on a Mamba layer is a Gated Memory Unit, ``(SiLU(n W_in) * memory)
W_out``, and attention attends over the kept keys and values
(``cross_attention``). What a layer keeps is handed to the later layers
beside the residual stream, so their gradients reach it.

The keys are the decoder's own where families spell one thing differently:
a spec written from HF's ``lfm2_moe`` gives its ``num_dense_layers`` as
``first_k_dense_replace``, its ``norm_eps`` as ``rms_norm_eps``, and its
router (``use_expert_bias`` true: sigmoid scores always, the ``expert_bias``
buffer in the choice, 1e-6 in the renormalisation) as ``scoring_func``
``sigmoid``, ``topk_method`` ``noaux_tc``, ``renorm_eps`` 1e-6; one written
from ``nemotron_h`` gives its ``layer_norm_epsilon`` as ``rms_norm_eps`` and
its router (sigmoid scores, ``e_score_correction_bias`` in the choice) as
``scoring_func`` ``sigmoid``, ``topk_method`` ``noaux_tc``.

``experts_held = (lo, hi)`` is the expert-parallel share of one chip: the
layer holds the weights of experts ``lo..hi-1`` only, still routes over all
of them, and returns its own experts' part of the sum (plus the shared
expert, which every chip computes alike). What the absent experts would add
is left out (on a mesh it arrives by the exchange; on one chip there is
none). The default holds every expert.

The selection bias (HF's ``e_score_correction_bias`` or ``expert_bias``, a
buffer there) is a leaf of ``params`` whose gradient is stopped: local
training and the average leave it as it came. Its update rule is a training
recipe no ``config.json`` gives, and a model cannot carry non-gradient state
through the round here.

Not expressed, and refused by name: a query latent (``q_lora_rank``),
group-limited routing (``n_group`` / ``topk_group`` over 1), rotary scaling
beside a latent (``rope_scaling``, a ``partial_rotary_factor`` other than 1
in ``rope_parameters``), QK norms beside a latent (``use_qk_norm``), head
counts per layer beside a latent or a one-part stack
(``num_attention_heads_per_layer``), or at another length than the stack's,
or not a multiple of ``num_key_value_heads``; a key of
``rope_parameters[kind]`` other than :data:`ROPE_KEYS`, or beside the kinds
other than ``original_max_position_embeddings`` (the fallback of a kind's
``yarn`` that gives none of its own); a ``conv`` layer without its filter's length
(``conv_L_cache``); a character of ``hybrid_override_pattern`` other than
``M``, ``E``, ``*``, ``-``; the pattern beside ``layer_types``,
``first_k_dense_replace`` or a latent (``kv_lora_rank``), or at another
length than ``num_hidden_layers``; ``n_groups`` that does not divide
``mamba_num_heads``; an ``M`` layer without its sizes; a clamp of the step
(``time_step_limit`` with a finite end or a positive start);
``mamba_hidden_act`` other than ``silu``; ``mlp_hidden_act`` other than
``relu2`` in a one-part stack, or other than ``silu`` in a stack of
``layer_types``; a ``-`` layer without ``intermediate_size``. No key here
gives a projection a bias (HF's ``attention_bias``, ``mlp_bias``,
``mamba_proj_bias``: an unknown key is refused by its name), a gated
short convolution a bias (HF's ``conv_bias`` true), documents packed into
one sequence (the scan, the convolutions and attention would have to reset
at their boundaries) or sequences over the attention kernel's
``MAX_LENGTH``. A second tower conditioned on this one and decoding
by diffusion over blocks have no key in any ``config.json`` read here and
no module: the decoder is one causal stack trained by next-token loss.

In training the model sows its counters per expert layer into the
``counters`` collection (:func:`counter_names`; ``ModelDef.apply(...,
counters=True)`` sums them over the layers); it returns logits only."""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Mapping, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from fedml_tpu.ops import attention as attention_op, grouped_matmul, slot_sum
from fedml_tpu.ops import rotary as rotary_op, selective_scan as sscan_op, ssd as ssd_op
from fedml_tpu.ops.attention import attention
from fedml_tpu.ops.rotary import rotary
from fedml_tpu.ops.selective_scan import selective_scan
from fedml_tpu.ops.short_conv import gated_short_conv, silu_short_conv
from fedml_tpu.ops.ssd import ssd

LAYER_KINDS = ("full_attention", "sliding_attention", "conv")
# The kinds of layer that call attention (``attention`` is a one-part stack's,
# ``cross_attention`` a SambaY cross-decoder's).
ATTENTION_KINDS = ("attention", "full_attention", "sliding_attention", "cross_attention")
# eps of the RMS norm over each differential pair's 2 x head_dim outputs
SUBLN_EPS = 1e-5
# What a layer kind's rotary parameters (``rope_parameters[kind]``) may hold.
ROPE_KEYS = ("rope_type", "rope_theta", "factor", "original_max_position_embeddings",
             "beta_fast", "beta_slow", "attention_factor", "partial_rotary_factor")
# The characters of ``hybrid_override_pattern`` and the one part each names.
PARTS = {"M": "mamba", "E": "experts", "*": "attention", "-": "mlp"}

# Per expert layer and call, as float32 (whole numbers below 2**24 a round):
# (token, slot) pairs routed to a held expert; held pairs that the dispatch
# left outside the grouped products (0 by construction: the check of the
# sort and of the passes taken); rows the grouped products ran over in this
# call (the row bound x the passes taken, not tokens x top-k); the largest
# and the mean number of pairs of one held expert; 1 for the call; 1 where
# the call's held pairs exceeded the row bound, so that it took a second pass.
COUNTERS = ("moe_pairs", "moe_dropped", "moe_rows", "moe_load_max", "moe_load_mean",
            "moe_calls", "moe_overflow")
# Only where the router has a selection bias, after the others: the chosen
# (token, slot) pairs that the top-k of the biased scores holds and the top-k
# of the scores themselves does not (over all experts, held or not).
BIAS_COUNTER = "moe_bias_moved"
SCORING = ("softmax", "sigmoid")


def counter_names(biased: bool):
    return COUNTERS + ((BIAS_COUNTER,) if biased else ())


def rotated_dims(rope: Mapping[str, Any], head_dim: int) -> int:
    """``R``, the dims of a head that a layer kind turns: ``head_dim x
    partial_rotary_factor`` (1 by default), as HF takes it (``int``), even
    and at least 2."""
    factor = float(rope.get("partial_rotary_factor", 1.0))
    R = int(head_dim * factor)
    if R % 2 or not 0 < R <= head_dim:
        raise ValueError(
            f"partial_rotary_factor {factor}: head_dim {head_dim} x factor has to be an even "
            f"number of dims in (0, {head_dim}], got {R}")
    return R


def rotary_tables(rope: Mapping[str, Any], head_dim: int, length: int):
    """(cos, sin), each [length, R] float32, of one layer kind: the angles
    of its ``R = rotated_dims(rope, head_dim)`` turned dims, frequencies and
    YaRN's correction range over ``R`` (HF's ``_compute_yarn_parameters``
    over ``head_dim x partial_rotary_factor``), ``attention_factor`` folded
    into both, so that it scales the turned dims and no other."""
    head_dim = rotated_dims(rope, head_dim)
    theta = float(rope["rope_theta"])
    m = jnp.arange(0, head_dim, 2, dtype=jnp.float32)
    inv_freq = theta ** (-m / head_dim)
    scale = 1.0
    kind = rope.get("rope_type", "default")
    if kind == "yarn":
        factor = float(rope["factor"])
        original = float(rope["original_max_position_embeddings"])

        def correction_dim(rotations):
            return head_dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(correction_dim(float(rope.get("beta_fast", 32)))), 0)
        high = min(math.ceil(correction_dim(float(rope.get("beta_slow", 1)))), head_dim - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
        inv_freq = (inv_freq / factor) * ramp + inv_freq * (1 - ramp)
        scale = float(rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0)
    elif kind != "default":
        raise ValueError(f"unknown rope_type {kind!r}; have 'default' and 'yarn'")
    angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def apply_rotary_pairs(x, cos, sin):
    """Rotary on the adjacent pairs ``(2m, 2m + 1)`` of x [B, T, H, D] where
    they lie (``cos`` and ``sin`` [T, D] hold each angle twice in a row), in
    float32, back in x's dtype."""
    x32 = x.astype(jnp.float32)
    even = jnp.arange(x.shape[-1]) % 2 == 0
    partner = jnp.where(even, -jnp.roll(x32, -1, axis=-1), jnp.roll(x32, 1, axis=-1))
    return (x32 * cos[None, :, None, :] + partner * sin[None, :, None, :]).astype(x.dtype)


def _any_batched(fn):
    """``fn`` with a vmap rule of its own: one call per member of the batch
    (the clients of a round; a static handful), each on its own slice.
    ``ragged_dot``'s rule wants every argument batched at dim 0, which the
    first pass over a scan's body under the clients' vmap does not give
    (the weights are not batched yet), the TPU compiler's grouped matmul
    takes no batch dimension at all, and ``ops/slot_sum.py``'s kernel loads
    its table by hand."""
    wrapped = custom_vmap(fn)

    @wrapped.def_vmap
    def rule(axis_size, in_batched, *args):
        outs = [fn(*(a[i] if b else a for a, b in zip(args, in_batched)))
                for i in range(axis_size)]
        return jnp.stack(outs), True

    return wrapped


def sum_readers(table, readers, weights=None):
    """Row n of the result is the float32 sum over k of
    ``table[readers[n, k]]`` (times ``weights[n, k]``); a reader of
    ``len(table)`` (out of bounds) reads zero. One gather of N rows per
    slot, added as it goes: XLA fuses each gather into the running sum, so
    the [N, top_k, d] array of a single gather under a ``sum`` is never
    written. On the v5e at 4 096 tokens of width 2 304, top-8 and 8 192
    table rows, with the row gather beside it: 0.68 ms forward (weighted)
    and 0.60 ms backward against 0.95 and 2.24 ms for the single gather,
    and 1.29 and 0.60 ms for segment sums (PERF.md section 6, PR 31)."""
    acc = None
    for k in range(readers.shape[1]):
        part = jnp.take(table, readers[:, k], axis=0, mode="fill", fill_value=0)
        part = part.astype(jnp.float32)
        if weights is not None:
            part = part * weights[:, k, None]
        acc = part if acc is None else acc + part
    return acc


_kernel_slots = _any_batched(
    lambda table, readers: slot_sum.slot_sum(table, readers, out_dtype=table.dtype))
_kernel_slots_weighted = _any_batched(
    lambda table, readers, weights: slot_sum.slot_sum(table, readers, weights))


def sum_slots(table, readers, weights=None):
    """:func:`sum_readers`'s sums, in float32 where weighted (the sum of a
    token's slots, which the overflow's chunks add to in float32) and
    rounded once to the table's dtype where not (the dispatch gather's
    backward): by ``ops/slot_sum.py``'s Pallas kernel, which reads only the
    rows of live slots, where its ``takes_kernel`` holds for the shapes
    (6 slots a token or more, a table of at most three eighths as many rows
    as slots: the expert cells' training steps but
    ``lfm2-8b-a1b.silo2t4k``'s), by ``sum_readers`` elsewhere."""
    N, top_k = readers.shape
    R, d = table.shape
    if not slot_sum.takes_kernel(N, top_k, d, R):
        total = sum_readers(table, readers, weights)
        return total if weights is not None else total.astype(table.dtype)
    if weights is None:
        return _kernel_slots(table, readers)
    return _kernel_slots_weighted(table, readers, weights)


@jax.custom_vjp
def take_rows(x, idx, readers):
    """``x[idx]`` where the rows of the result that read row ``r`` of ``x``
    and whose cotangent may be non-zero are exactly ``readers[r]``, a fixed
    number of places each, the places that hold no reader filled with
    ``len(idx)`` (out of bounds, read as zero): the backward pass is then
    gathers too (:func:`sum_slots`), not a scatter-add. A row that no
    reader names must have a zero cotangent (``_held_rows`` clears those
    rows)."""
    return x[idx]


def _take_rows_fwd(x, idx, readers):
    return x[idx], readers


def _take_rows_bwd(readers, g):
    return sum_slots(g, readers), None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def weighted_rows(ys, top_w, readers, slot):
    """Token n's sum of ``top_w[n, k] * ys[readers[n, k]]`` over its slots,
    in float32 (:func:`sum_slots`). ``slot`` [len(ys)] is the flat
    (token, slot) place that reads each row, so the backward pass runs over
    the rows of ``ys``, not over tokens x top-k."""
    return sum_slots(ys, readers, top_w)


def _weighted_rows_fwd(ys, top_w, readers, slot):
    return weighted_rows(ys, top_w, readers, slot), (ys, top_w, readers, slot)


def _weighted_rows_bwd(res, g):
    ys, top_w, readers, slot = res
    g_rows = g[slot // top_w.shape[1]]
    by_row = jnp.sum(ys.astype(jnp.float32) * g_rows, axis=1)
    return (
        (g_rows * top_w.reshape(-1)[slot][:, None]).astype(ys.dtype),
        jnp.take(by_row, readers, mode="fill", fill_value=0),
        None, None,
    )


weighted_rows.defvjp(_weighted_rows_fwd, _weighted_rows_bwd)


# lhs [M, K] and rhs [M, N], both ragged over M: the groups' K x N products.
_TO_WEIGHTS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[],
)


_rows_by_group = _any_batched(
    lambda rows, weights, sizes: jax.lax.ragged_dot(rows, weights, sizes))
_weights_by_group = _any_batched(
    lambda rows, cot, sizes: jax.lax.ragged_dot_general(rows, cot, sizes, _TO_WEIGHTS))
_kernel_rows = _any_batched(
    lambda rows, weights, sizes: grouped_matmul.gmm(rows, weights, sizes))
_kernel_rows_t = _any_batched(
    lambda cot, weights, sizes: grouped_matmul.gmm(cot, weights, sizes, transposed=True))
_kernel_weights = _any_batched(
    lambda rows, cot, sizes: grouped_matmul.tgmm(rows, cot, sizes))


def _takes_kernel(rows, weights) -> bool:
    """``grouped_matmul.takes_kernel`` at a site's forward shape: its three
    products take one path."""
    G, K, N = weights.shape
    return grouped_matmul.takes_kernel(rows.shape[0], K, N, G)


def _forward(rows, weights, sizes):
    by_group = _kernel_rows if _takes_kernel(rows, weights) else _rows_by_group
    return by_group(rows, weights, sizes)


@jax.custom_vjp
def grouped_dot(rows, weights, sizes):
    """Grouped product: row r of the result is ``rows[r] @ weights[g]`` for
    the group g that r lies in, the groups being consecutive runs of
    ``sizes[g]`` rows. rows [M, K], weights [G, K, N], sizes [G] -> [M, N].
    Where ``ops/grouped_matmul.takes_kernel`` holds for the shapes, the
    product and both gradients are that module's Pallas kernels
    (``gmm_fwd``, ``gmm_dx`` reading the weights as they lie, ``gmm_dw``);
    elsewhere XLA's ``ragged_dot``. Either way the work follows the rows
    inside the groups and the sums are float32; differentiable and batchable
    under both client schedules; rows after the last group hold nothing to
    rely on."""
    return _forward(rows, weights, sizes)


def _grouped_dot_fwd(rows, weights, sizes):
    return _forward(rows, weights, sizes), (rows, weights, sizes)


def _grouped_dot_bwd(res, g):
    rows, weights, sizes = res
    if _takes_kernel(rows, weights):
        return (
            _kernel_rows_t(g, weights, sizes),
            _kernel_weights(rows, g, sizes).astype(weights.dtype),
            None,
        )
    return (
        _rows_by_group(g, jnp.swapaxes(weights, 1, 2), sizes),
        _weights_by_group(rows, g, sizes).astype(weights.dtype),
        None,
    )


grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


# Sorted rows come in multiples of this many: a whole number of the MXU's 128
# rows and of the (8, 128) / (16, 128) tiles that float32 / bfloat16 rows lie
# in, so no chunk of rows starts inside a tile. Not tuned: the one cell that
# holds a share of its experts has a bound of 8 192 rows whatever this is.
ROW_TILE = 512


def row_bound(rows: int, held: int, experts: int) -> int:
    """The sorted (token, slot) rows one pass of the expert products takes:
    twice the even share ``rows * held / experts`` of a chip that holds
    ``held`` of ``experts`` experts, in whole ``ROW_TILE``s, and never more
    than ``rows`` (which it is where every expert is held)."""
    twice_even = -(-2 * rows * held // experts)
    return min(rows, -(-twice_even // ROW_TILE) * ROW_TILE)


@functools.partial(jax.jit, static_argnames="bound")
def _held_rows(c, x, top_w, *rest, bound):
    """What the sorted rows ``[c * bound, (c + 1) * bound)`` add to every
    token's sum: [N, d] float32. ``rest`` is the experts' weights (``w_gate,
    w_up, w_down`` of gated-SiLU experts, three grouped products; ``w_up,
    w_down`` of ungated ReLU-squared ones, two) and then ``order`` (padded
    to whole chunks) and ``inverse`` [N, top_k], the sort and its inverse,
    and ``group_sizes``, the held experts' pair counts over all rows. Jitted so that every layer
    and both places that call it (inline and in the overflow's loop, forward
    and backward) share one traced and lowered function: tracing and
    lowering four layers' gradient takes 0.9 s so, 1.9 s without (host
    seconds), and the compiled program is the same."""
    *weights, order, inverse, group_sizes = rest
    top_k = top_w.shape[1]
    start = c * bound
    with jax.named_scope("dispatch"):
        slot = jax.lax.dynamic_slice_in_dim(order, start, bound)
        ends = jnp.cumsum(group_sizes)
        sizes = jnp.maximum(
            jnp.minimum(ends, start + bound) - jnp.maximum(ends - group_sizes, start), 0)
        # sorted rows that carry a pair of a held expert; the grouped products
        # leave whatever they find in the rows after them (on the chip: not
        # zeros), so every result is cleared there
        live = (jnp.arange(bound) < ends[-1] - start)[:, None]
        # a slot reads its row only where the row is live: the bound's other
        # rows hold pairs of experts on other chips, which read zero (a
        # cleared row forward, a cleared row's zero cotangent backward), so
        # the token-side sums need not visit them
        at = inverse - start
        readers = jnp.where((at >= 0) & (at < jnp.minimum(bound, ends[-1] - start)), at, bound)
        xs = take_rows(x, slot // top_k, readers)
        xs = jnp.where(live, xs, jnp.zeros((), xs.dtype))
    with jax.named_scope("experts"):
        if len(weights) == 3:
            w_gate, w_up, w_down = weights
            gate = grouped_dot(xs, w_gate, sizes)
            up = grouped_dot(xs, w_up, sizes)
            hidden = jnp.where(live, jax.nn.silu(gate) * up, jnp.zeros((), up.dtype))
        else:
            w_up, w_down = weights
            up = grouped_dot(xs, w_up, sizes)
            hidden = jnp.where(live, jnp.square(jax.nn.relu(up)), jnp.zeros((), up.dtype))
        ys = grouped_dot(hidden, w_down, sizes)
        ys = jnp.where(live, ys, jnp.zeros((), ys.dtype))
    with jax.named_scope("combine"):
        return weighted_rows(ys, top_w, readers, slot)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _sum_chunks(part, used, operands, index):
    """``part(c, *operands, *index)`` summed over the chunks ``c < used``
    (``used >= 1``): chunk 0 inline, the others in a loop of ``used - 1``
    trips, none on a step that stays under the bound.

    What a chunk keeps for the backward pass follows from the one thing the
    program knows of it. Chunk 0 runs on every step, so it is differentiated
    as ordinary JAX code and keeps its residuals: of :func:`_held_rows` the
    sorted rows ``xs`` and ``ys`` [bound, d], ``gate``, ``up`` and ``hidden``
    [bound, f], ``bound * (2 d + 3 f)`` numbers of the compute dtype a layer
    (151 MB at 8 192 rows of 2 304 and 896 in bfloat16), beside the readers
    and ``live``; its backward pass is the six grouped products and the row
    passes of the gradient alone. The overflow's chunks run a number of
    times that is data, so their residuals have no shape to be kept in: the
    backward pass is written out as the same loop over each chunk's own vjp,
    which runs the chunk again. Neither a ``cond`` nor a ``scan`` is
    differentiated, so no chunk that does not run writes zeros for
    residuals, and both passes add the chunks up in the same order."""
    y = part(jnp.int32(0), *operands, *index)
    return jax.lax.fori_loop(1, used, lambda c, y: y + part(c, *operands, *index), y)


def _sum_chunks_fwd(part, used, operands, index):
    y, pull_first = jax.vjp(lambda *ops: part(jnp.int32(0), *ops, *index), *operands)
    y = jax.lax.fori_loop(1, used, lambda c, y: y + part(c, *operands, *index), y)
    return y, (pull_first, used, operands, index)


def _sum_chunks_bwd(part, res, g):
    pull_first, used, operands, index = res

    def pull(c):
        return jax.vjp(lambda *ops: part(c, *ops, *index), *operands)[1](g)

    grads = jax.lax.fori_loop(
        1, used, lambda c, acc: jax.tree_util.tree_map(jnp.add, acc, pull(c)), pull_first(g))
    return None, grads, None


_sum_chunks.defvjp(_sum_chunks_fwd, _sum_chunks_bwd)


def routed_experts(x, router, w_gate, w_up, w_down, bias=None, *, top_k: int,
                   norm_topk_prob: bool = True, held_from: int = 0,
                   scoring: str = "softmax", scale: float = 1.0, renorm_eps: float = 0.0):
    """The held experts' part of a routed expert layer.

    x [N, d] tokens; router [d, E]; w_gate, w_up [Eh, d, f] and w_down
    [Eh, f, d], the weights of experts ``held_from .. held_from + Eh - 1``.
    Routes every token over all E experts (logits and scores in float32),
    sorts the N*top_k (token, slot) pairs by expert with the pairs of absent
    experts last, runs the three products as grouped products
    (:func:`grouped_dot`) over the held experts' rows, and sums each
    token's held slots by its (renormalised) top-k weights. With ``w_gate``
    ``None`` the experts are ungated, ``relu(x W_up)**2 W_down``: two grouped
    products forward and four backward where a gated expert runs three and
    six, and ``R * (2 d + 2 f)`` numbers kept (``xs``, ``ys``; ``up``,
    ``hidden``); everything around the products is the same code. Returns
    ``(y [N, d], counters [len(counter_names(bias is not None))] float32)``.

    ``scoring`` is ``softmax`` over the experts or ``sigmoid`` of each logit.
    ``bias`` [E] (HF's ``e_score_correction_bias``) is added to the scores
    for the choice of the top-k alone: a slot's weight is its expert's own
    score, and no gradient reaches the bias. The chosen weights are
    renormalised over ``sum + renorm_eps`` (HF's sigmoid routers add 1e-20
    or 1e-6; 0 adds nothing), and ``scale`` (``routed_scaling_factor``)
    multiplies the weights last. With softmax, no bias, scale 1 and no
    epsilon the traced program is what it was before any of the four
    existed.

    The rows between the sort and the sum are bounded by shapes alone:
    ``R = row_bound(N * top_k, Eh, E)``, twice the even share of this chip's
    experts. The gather of ``x``, the products, the SiLU and the clears take
    the first R sorted rows (the held pairs are sorted first). A step that
    routes more than R pairs here runs the same R-row computation again on
    the next R sorted rows, as often as it takes (:func:`_sum_chunks`): no
    capacity, no dropped pair, the same function of the weights; only the
    float32 sum of a token's slots may be taken in another order. The first
    R rows, which every step runs, keep what their backward pass reads:
    ``R * (2 d + 3 f) * itemsize`` bytes a layer (``xs``, ``ys``; ``gate``,
    ``up``, ``hidden``) beside the [N, E] float32 scores and the sort's
    [N*top_k] int32 vectors, so a gradient holds nine grouped products a
    layer outside the overflow's loops, whose passes alone are run again.
    Where every expert is held R is N*top_k, those bytes are
    ``N * top_k * (2 d + 3 f) * itemsize``, and there is neither a loop nor
    a ``custom_vjp`` around the pass in the program.
    Two passes are indexed by token: the sum of a token's slots
    (:func:`weighted_rows`) and the backward of the dispatch gather
    (:func:`take_rows`), both :func:`sum_slots`, which reads only the rows
    of live slots where ``ops/slot_sum.takes_kernel`` holds and N x top_k
    rows (:func:`sum_readers`) elsewhere.

    Under a ``vmap`` (the clients of a round) the loop's trip count is
    batched, and JAX runs as many trips as the member with the most pairs
    needs, masking the others: the result is each member's own."""
    N, d = x.shape
    Eh = w_up.shape[0]
    rows = N * top_k
    bound = row_bound(rows, Eh, router.shape[1])
    chunks = -(-rows // bound)
    with jax.named_scope("router"):
        logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
        if scoring == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
        elif scoring == "sigmoid":
            probs = jax.nn.sigmoid(logits)
        else:
            raise ValueError(f"unknown scoring_func {scoring!r}; have {SCORING}")
        if bias is None:
            top_w, top_e = jax.lax.top_k(probs, top_k)
        else:
            _, top_e = jax.lax.top_k(probs + jax.lax.stop_gradient(bias.astype(probs.dtype)), top_k)
            top_w = jnp.take_along_axis(probs, top_e, axis=-1)
            # a chosen pair's rank among the scores themselves (ties to the
            # lower index, as top_k breaks them): from top_k on, the bias chose it
            ahead = (probs[:, None, :] > top_w[:, :, None]) | (
                (probs[:, None, :] == top_w[:, :, None])
                & (jnp.arange(probs.shape[1])[None, None, :] < top_e[:, :, None]))
            moved = jnp.sum(jnp.sum(ahead, axis=-1) >= top_k)
        if norm_topk_prob:
            total = jnp.sum(top_w, axis=-1, keepdims=True)
            top_w = top_w / (total + renorm_eps if renorm_eps else total)
        if scale != 1.0:
            top_w = top_w * scale
    with jax.named_scope("dispatch"):
        local = top_e.reshape(rows) - held_from
        held = (local >= 0) & (local < Eh)
        key = jnp.where(held, local, Eh)
        order = jnp.argsort(key, stable=True)
        inverse = jnp.argsort(order)
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(Eh, dtype=key.dtype)[None, :], axis=0, dtype=jnp.int32)
        pairs = jnp.sum(group_sizes)
        # chunks of `bound` sorted rows that hold a held pair; the first always runs
        used = jnp.clip(-(-pairs // bound), 1, chunks)
    part = functools.partial(_held_rows, bound=bound)
    operands = (x, top_w) + (() if w_gate is None else (w_gate,)) + (w_up, w_down)
    index = (jnp.pad(order, (0, chunks * bound - rows)), inverse.reshape(N, top_k), group_sizes)
    if chunks == 1:
        y = part(jnp.int32(0), *operands, *index)
    else:
        y = _sum_chunks(part, used, operands, index)
    with jax.named_scope("combine"):
        y = y.astype(x.dtype)
        f32 = jnp.float32
        counters = jnp.stack([
            pairs.astype(f32),
            jnp.sum(held[order] & (jnp.arange(rows) >= jnp.minimum(pairs, used * bound))).astype(f32),
            (used * bound).astype(f32),
            jnp.max(group_sizes).astype(f32),
            pairs.astype(f32) / Eh,
            jnp.ones((), f32),
            (pairs > bound).astype(f32),
        ] + ([] if bias is None else [moved.astype(f32)]))
    return y, jax.lax.stop_gradient(counters)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + self.eps) * scale).astype(x.dtype)


@functools.partial(jax.checkpoint, static_argnums=(3,))
def layer_norm(x, scale, bias, eps: float):
    """LayerNorm with a learned scale and bias: statistics, scale and bias in
    float32, one rounding to x's dtype. A ``jax.checkpoint``: between the
    passes it keeps ``x`` as it came (bfloat16 in the training cell) where
    autodiff would keep float32 arrays of its size."""
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    return (centred * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


class LayerNorm(nn.Module):
    """:func:`layer_norm` with its two leaves, ``scale`` (ones) and ``bias``
    (zeros)."""

    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        return layer_norm(x, scale, bias, self.eps)


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    """One layer's attention, in numbers. ``rope_dim`` 0 is grouped-query
    attention (``head_dim`` for q, k and v, rotary on all of it); otherwise
    latent attention: ``head_dim`` without rotary and ``rope_dim`` with it a
    query head, values of ``v_dim``, keys and values out of a latent of
    ``kv_lora_rank``. ``qk_norm``: an RMS norm with a learned scale over
    each head's dims of q and of k, ahead of rotary (grouped-query only).
    ``rotary`` false: q and k go to the scores as projected, no positions
    (the attention of a one-part stack, whose state-space parts carry the
    order; grouped-query only). ``rotary_dim``: the first dims of a head
    that rotary turns, the others passing (grouped-query only); 0 turns the
    whole head."""

    heads: int
    kv_heads: int
    head_dim: int
    rope_dim: int = 0
    v_dim: int = 0
    kv_lora_rank: int = 0
    interleave: bool = False
    qk_norm: bool = False
    rotary: bool = True
    rotary_dim: int = 0
    differential: bool = False

    def site(self) -> Tuple[int, ...]:
        """The shapes ``ops/attention.attention`` is called with, as
        ``takes_kernel`` takes them after the length
        (``DecoderLM.attention_sites``): what the layer below computes from
        and what the round's ``flush`` span reports cannot drift apart. A
        differential map is half the query heads on half the key heads, its
        values two key heads wide."""
        if self.differential:
            return (self.heads // 2, self.kv_heads // 2, self.head_dim, 0, 2 * self.head_dim)
        latent = (self.rope_dim, self.v_dim) if self.rope_dim else ()
        return (self.heads, self.kv_heads, self.head_dim) + latent

    def sites(self) -> Tuple[Tuple[int, ...], ...]:
        """A layer's calls of ``ops/attention.attention``: one, or a
        differential layer's two maps."""
        return (self.site(),) * (2 if self.differential else 1)

    def rope_sites(self) -> Tuple[Tuple[int, ...], ...]:
        """The (heads, dims a head) of each call of ``ops/rotary.rotary`` in
        a layer, and the dims it turns where that is not the whole head, as
        its ``takes_kernel`` takes them after the length
        (``DecoderLM.rope_sites``); the pairs form is no such call."""
        if self.interleave or not self.rotary:
            return ()
        if self.rope_dim:
            return ((self.heads, self.rope_dim), (1, self.rope_dim))
        dims = (self.head_dim,) + ((self.rotary_dim,) if self.rotary_dim else ())
        return ((self.heads,) + dims, (self.kv_heads,) + dims)


@dataclasses.dataclass(frozen=True)
class ExpertSpec:
    """One expert layer, in numbers (:func:`routed_experts` has the rules)."""

    experts: int
    top_k: int
    width: int
    held: Tuple[int, int]
    norm_topk_prob: bool = True
    scoring: str = "softmax"
    biased: bool = False
    scale: float = 1.0
    shared_width: int = 0
    renorm_eps: float = 0.0
    gated: bool = True

    def products(self) -> int:
        """Grouped products a held pair runs forward: the span constant
        ``expert_products`` that a reader's FLOPs a pair follow from."""
        return 3 if self.gated else 2

    def grouped_sites(self, hidden: int, tokens: int) -> Tuple[Tuple[int, int, int, int], ...]:
        """``DecoderLM.grouped_sites`` of one layer over ``tokens``: gate and
        up ``d -> f`` (up alone where ungated), down ``f -> d``, each on the
        bounded rows of its held experts."""
        held = self.held[1] - self.held[0]
        rows = row_bound(tokens * self.top_k, held, self.experts)
        return ((rows, hidden, self.width, held),) * (self.products() - 1) + (
            (rows, self.width, hidden, held),)

    def slot_sites(self, hidden: int, tokens: int) -> Tuple[Tuple[int, int, int, int], ...]:
        """``DecoderLM.slot_sites`` of one layer over ``tokens``: the sum of a
        token's slots and the backward of the dispatch gather, each over the
        bounded rows."""
        held = self.held[1] - self.held[0]
        rows = row_bound(tokens * self.top_k, held, self.experts)
        return ((tokens, self.top_k, hidden, rows),) * 2


@dataclasses.dataclass(frozen=True)
class MambaSpec:
    """One Mamba-2 mixer, in numbers (:meth:`DecoderLayer.mamba` has the
    equations): ``heads`` of ``head_dim`` (their product is the inner
    width), ``groups`` that share B and C of ``state`` numbers, a
    convolution of ``taps`` over ``x | B | C`` with or without a bias, the
    scan's ``chunk``, and what the step's bias is drawn from."""

    heads: int
    head_dim: int
    groups: int
    state: int
    taps: int
    chunk: int = 128
    conv_bias: bool = True
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_width(self) -> int:
        return self.inner + 2 * self.groups * self.state


@dataclasses.dataclass(frozen=True)
class Mamba1Spec:
    """One Mamba-1 mixer or Gated Memory Unit of a SambaY stack, in numbers
    (:meth:`DecoderLayer.mamba_one` has the equations): ``inner`` channels
    (``mamba_expand x hidden_size``), a state of ``state`` numbers a channel,
    a convolution of ``taps`` with a bias, the step's low rank ``dt_rank``
    (``ceil(hidden_size / 16)``, Mamba's own), and what the step's bias is
    drawn from."""

    inner: int
    state: int
    taps: int
    dt_rank: int
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4


def gated_mlp(x, w_gate, w_up, w_down):
    return jnp.dot(jax.nn.silu(jnp.dot(x, w_gate)) * jnp.dot(x, w_up), w_down)


def relu2_mlp(x, w_up, w_down):
    return jnp.dot(jnp.square(jax.nn.relu(jnp.dot(x, w_up))), w_down)


@jax.checkpoint
def silu_gate(gate, up):
    """``SiLU(gate) * up`` in float32, rounded once to ``up``'s dtype, and
    recomputed in the backward pass: between the passes it keeps its two
    operands, which the products' gradients read anyway, and not the
    product, which only the next product's gradient reads (84 MB a layer in
    bfloat16 at 4 096 tokens of 10 240)."""
    return (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(up.dtype)


@functools.partial(jax.checkpoint, static_argnums=(4,))
def differential_combine(first, second, lambdas, scale, lambda_init: float):
    """``RMSNorm(first - lambda second) * scale * (1 - lambda_init)`` over the
    last axis (a pair's 2 x head_dim), ``lambda = exp(lq1 . lk1) - exp(lq2 .
    lk2) + lambda_init`` from the four ``lambdas``; float32, one rounding to
    ``first``'s dtype. A ``jax.checkpoint``: between the passes it keeps the
    two maps' outputs (the attention kernels keep them anyway) and not the
    float32 difference and norm of their size."""
    lq1, lk1, lq2, lk2 = (v.astype(jnp.float32) for v in lambdas)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lambda_init
    o = first.astype(jnp.float32) - lam * second.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + SUBLN_EPS)
    return (o * scale.astype(jnp.float32) * (1.0 - lambda_init)).astype(first.dtype)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``GroupRMSNorm(y * SiLU(z)) * scale`` over ``groups`` equal runs of the
    last axis, the gate first (HF's ``MambaRMSNormGated``): product,
    statistics and scale in float32, one rounding to ``y``'s dtype. A
    ``jax.checkpoint``: between the passes it keeps ``y`` and ``z`` as they
    came (bfloat16 in the training cells) where autodiff would keep four
    float32 arrays of their size (0.27 GB a layer at 4 096 tokens of 4 096),
    and recomputes a SiLU, two products and a mean."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = g.reshape(*g.shape[:-1], groups, g.shape[-1] // groups)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return (g.reshape(y.shape) * scale).astype(y.dtype)


def a_log_init(key, shape, dtype=jnp.float32):
    """``log(a)``, ``a`` uniform in [1, 16]: the source's own draw."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def symmetric_uniform(bound: float):
    """Uniform in [-bound, bound): Mamba's draw of ``dt_proj`` at
    ``dt_rank ** -0.5``."""

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


def a_log_range_init(key, shape, dtype=jnp.float32):
    """Mamba-1's ``A_log`` [channels, N]: ``log(1..N)`` in every channel."""
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1, dtype=dtype)), shape)


def dt_bias_init(dt_min: float, dt_max: float, dt_floor: float):
    """The inverse softplus of a step drawn log-uniformly in [dt_min, dt_max]
    and floored at dt_floor, so that ``softplus(dt_bias)`` is that step."""

    def init(key, shape, dtype=jnp.float32):
        u = jax.random.uniform(key, shape, dtype)
        dt = jnp.exp(u * (math.log(dt_max) - math.log(dt_min)) + math.log(dt_min))
        dt = jnp.maximum(dt, dt_floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    return init


class DecoderLayer(nn.Module):
    """One layer. Every weight is a leaf of the layer itself (the latent's
    inner norm is a module), and every part of the computation a
    ``jax.named_scope`` directly beneath it, so a device trace splits the
    layer by them: ``qkv``, ``qk_norm`` (where the spec has it), ``rope``,
    ``attention_full`` / ``attention_sliding``, ``out`` (grouped-query),
    ``q_proj``, ``kv_latent``, ``rope``, ``attention_mla``, ``out``
    (latent), ``in_proj``, ``short_conv``, ``out`` (a ``conv`` layer),
    ``mlp`` (a dense layer), ``router``, ``dispatch``, ``experts``,
    ``combine``, ``shared`` (an expert layer). The helpers below are
    ``nowrap``: a wrapped method would put a scope of its own
    (``layers_0.latent``) between the layer and these. ``ffn`` is the dense
    MLP's width or the expert layer's numbers; ``conv_taps`` the filter's
    length in a ``conv`` layer, whose mixer needs neither ``attn`` nor the
    rotary tables.

    A ``kind`` of :data:`PARTS` is a layer of ONE part under ONE norm (the
    leaf ``norm``), ``x + Part(RMSNorm(x))``: ``mamba`` (``in_proj``,
    ``conv``, ``ssd`` with the operator's ``ssd_chunk``, ``ssd_state`` and
    ``ssd_out`` beneath it, ``gated_norm``, ``out``; numbers in ``ssm``),
    ``attention`` (``qkv``, ``attention_full``, ``out`` and, where the spec
    has no rotary, no ``rope``), ``experts`` (the five scopes above) or
    ``mlp``. Its ``ffn`` is ``None`` where the part is a mixer.

    A layer of a SambaY stack (``layer_norm``: LayerNorms with a bias, eps
    ``rms_norm_eps``, and a dense gated-SiLU MLP) is called with and returns
    ``carried``, what earlier layers hand on: ``memory``, the scan output of
    the Mamba-1 layer that ``keeps`` it, and ``kv``, the keys and values of
    the full attention layer that ``keeps`` them. Its scopes: ``in_proj``,
    ``conv``, ``x_proj``, ``selective_scan``, ``gate``, ``out`` (``mamba1``);
    ``gmu``, ``out`` (``gmu``); ``qkv`` (``q`` on a cross layer),
    ``attention_sliding`` / ``attention_full`` / ``attention_cross``,
    ``diff_combine``, ``out`` (differential attention); ``mlp``.
    ``lambda_init`` is the differential layer's, set from its published
    index."""

    kind: str
    attn: AttentionSpec
    ffn: Union[int, ExpertSpec, None]
    sliding_window: int
    rms_norm_eps: float
    conv_taps: int = 0
    ssm: Optional[MambaSpec] = None
    mamba1: Optional[Mamba1Spec] = None
    layer_norm: bool = False
    lambda_init: float = 0.0
    keeps: bool = False

    @nn.nowrap
    def grouped_query(self, n, cos, sin, init):
        B, T, d = n.shape
        H, KV, D = self.attn.site()
        with jax.named_scope("qkv"):
            q = jnp.dot(n, self.param("q_proj", init, (d, H * D))).reshape(B, T, H, D)
            k = jnp.dot(n, self.param("k_proj", init, (d, KV * D))).reshape(B, T, KV, D)
            v = jnp.dot(n, self.param("v_proj", init, (d, KV * D))).reshape(B, T, KV, D)
        if self.attn.qk_norm:
            # one scale of D for all heads of q, one for k
            with jax.named_scope("qk_norm"):
                q = RMSNorm(self.rms_norm_eps, name="q_layernorm")(q)
                k = RMSNorm(self.rms_norm_eps, name="k_layernorm")(k)
        if self.attn.rotary:
            with jax.named_scope("rope"):
                q, k = rotary(q, cos, sin), rotary(k, cos, sin)
        sliding = self.kind == "sliding_attention"
        with jax.named_scope("attention_sliding" if sliding else "attention_full"):
            return attention(
                q, k, v, causal=True, window=self.sliding_window if sliding else None)

    @nn.nowrap
    def latent(self, n, cos, sin, init):
        B, T, d = n.shape
        H, _, D, R, V = self.attn.site()
        rank = self.attn.kv_lora_rank
        with jax.named_scope("q_proj"):
            q = jnp.dot(n, self.param("q_proj", init, (d, H * (D + R)))).reshape(B, T, H, D + R)
        with jax.named_scope("kv_latent"):
            down = jnp.dot(n, self.param("kv_a_proj", init, (d, rank + R)))
            latent = RMSNorm(self.rms_norm_eps, name="kv_a_layernorm")(down[..., :rank])
            kv = jnp.dot(latent, self.param("kv_b_proj", init, (rank, H * (D + V))))
            kv = kv.reshape(B, T, H, D + V)
        with jax.named_scope("rope"):
            turn = apply_rotary_pairs if self.attn.interleave else rotary
            q_rope = turn(q[..., D:], cos, sin)
            k_rope = turn(down[..., None, rank:], cos, sin)
        with jax.named_scope("attention_mla"):
            return attention(
                q[..., :D], kv[..., :D], kv[..., D:], causal=True,
                window=self.sliding_window if self.kind == "sliding_attention" else None,
                q_rope=q_rope, k_rope=k_rope, scale=(D + R) ** -0.5)

    @nn.nowrap
    def short_conv(self, n, init):
        """The gated short convolution between its two projections'
        products (``ops/short_conv.py``): [B, T, d]. The filter starts at
        deviation ``1 / sqrt(taps)``, so that the sum over the taps keeps its
        input's scale."""
        d, L = n.shape[-1], self.conv_taps
        with jax.named_scope("in_proj"):
            bcx = jnp.dot(n, self.param("in_proj", init, (d, 3 * d)))
        with jax.named_scope("short_conv"):
            return gated_short_conv(
                bcx, self.param("conv", nn.initializers.normal(L ** -0.5), (d, L)))

    @nn.nowrap
    def mamba(self, n, init):
        """The Mamba-2 mixer (HF's ``NemotronHMamba2Mixer``) ahead of its
        output projection: [B, T, heads x head_dim].

            [z | xBC | dt] = n W_in        d -> inner + (inner + 2 G N) + H
            xBC = SiLU(conv(xBC) + b)      causal, depthwise, ``taps`` a channel
            [x | B | C] = xBC              inner | G x N | G x N
            step = softplus(dt + dt_bias), A = -exp(A_log)          float32
            y = ssd(x, step, A, B, C, D)   ops/ssd.py: the scan over positions
            y = GroupRMSNorm(y * SiLU(z)) * w      groups of inner / G, gate first

        The step has no clamp (``time_step_limit`` [0, inf)). The gated norm's
        product, statistics and scale are float32, rounded once."""
        B_, T, d = n.shape
        m = self.ssm
        H, P, G, N = m.heads, m.head_dim, m.groups, m.state
        with jax.named_scope("in_proj"):
            zxbcdt = jnp.dot(n, self.param("in_proj", init, (d, m.inner + m.conv_width + H)))
            z, xbc, dt = jnp.split(zxbcdt, [m.inner, m.inner + m.conv_width], axis=-1)
        with jax.named_scope("conv"):
            xbc = silu_short_conv(
                xbc, self.param("conv", nn.initializers.normal(m.taps ** -0.5), (m.conv_width, m.taps)),
                self.param("conv_bias", nn.initializers.zeros, (m.conv_width,)) if m.conv_bias
                else None)
        with jax.named_scope("ssd"):
            step = jax.nn.softplus(dt.astype(jnp.float32) + self.param(
                "dt_bias", dt_bias_init(m.dt_min, m.dt_max, m.dt_floor), (H,)).astype(jnp.float32))
            A = -jnp.exp(self.param("A_log", a_log_init, (H,)).astype(jnp.float32))
            x, Bm, Cm = jnp.split(xbc, [m.inner, m.inner + G * N], axis=-1)
            y = ssd(x.reshape(B_, T, H, P), step, A, Bm.reshape(B_, T, G, N),
                    Cm.reshape(B_, T, G, N), self.param("D", nn.initializers.ones, (H,)), m.chunk)
        with jax.named_scope("gated_norm"):
            return gated_group_norm(
                y.reshape(B_, T, m.inner), z, self.param("gated_norm", nn.initializers.ones, (m.inner,)),
                G, self.rms_norm_eps)

    @nn.nowrap
    def differential(self, n, kv, init):
        """Differential attention (arXiv:2410.05258, HF's ``phi4flash``): [B,
        T, heads x head_dim] and the keys and values it read, ``(k1, k2, V)``.

            [q | k | v] = n Wqkv         heads + 2 kv_heads of head_dim, no rotary
            q1, q2 = q's halves; k1, k2 and v1, v2 = k's and v's halves
            V_g = [v1_g | v2_g]          two heads of values a key head
            o_i = softmax(q1_i k1_g^T / sqrt(D)) V_g
                  - lambda softmax(q2_i k2_g^T / sqrt(D)) V_g      g = i // 2, causal
            lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
            o_i <- RMSNorm(o_i) * (1 - lambda_init)                 over 2 D, learned scale

        A cross layer projects q alone (``Wq``) and reads ``kv``, the keys
        and values of the layer that kept them. Each map is one call of
        ``ops/attention.attention`` with values twice as wide as keys; lambda,
        the difference and the norm are float32, rounded once."""
        B, T, d = n.shape
        H, KV, D = self.attn.heads, self.attn.kv_heads, self.attn.head_dim
        if self.kind == "cross_attention":
            with jax.named_scope("q"):
                q = jnp.dot(n, self.param("Wq", init, (d, H * D))).reshape(B, T, H, D)
            k1, k2, v = kv
        else:
            with jax.named_scope("qkv"):
                qkv = jnp.dot(n, self.param("Wqkv", init, (d, (H + 2 * KV) * D)))
                q, k, v = jnp.split(qkv, [H * D, (H + KV) * D], axis=-1)
                q = q.reshape(B, T, H, D)
                k, v = k.reshape(B, T, KV, D), v.reshape(B, T, KV, D)
                k1, k2 = k[:, :, :KV // 2], k[:, :, KV // 2:]
                v = jnp.concatenate([v[:, :, :KV // 2], v[:, :, KV // 2:]], axis=-1)
        window = self.sliding_window if self.kind == "sliding_attention" else None
        with jax.named_scope("attention_" + self.kind.split("_")[0]):
            first = attention(q[:, :, :H // 2], k1, v, causal=True, window=window)
            second = attention(q[:, :, H // 2:], k2, v, causal=True, window=window)
        with jax.named_scope("diff_combine"):
            o = differential_combine(
                first, second,
                [self.param(name, nn.initializers.normal(0.1), (D,))
                 for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")],
                self.param("subln", nn.initializers.ones, (2 * D,)), self.lambda_init)
        return o.reshape(B, T, H // 2 * 2 * D), (k1, k2, v)

    @nn.nowrap
    def mamba_one(self, n, init):
        """The Mamba-1 mixer (arXiv:2312.00752; HF's ``phi4flash``
        ``Phi3Mamba``) ahead of its output projection: the gated output [B,
        T, inner] and the scan's output ``y``, the memory a SambaY stack hands
        on (taken before the gate).

            [u | z] = n W_in                 d -> 2 inner, no bias
            u = SiLU(conv(u) + b)            causal, depthwise, ``taps`` a channel
            [dt_r | B | C] = u W_x           inner -> dt_rank + N + N, no bias
            dt = dt_r W_dt                   dt_rank -> inner (its bias in the scan)
            y = selective_scan(u, dt, -exp(A_log), B, C, D, dt_bias)
            out = y * SiLU(z)

        The step, the decays and the states are float32
        (``ops/selective_scan.py``)."""
        d, m = n.shape[-1], self.mamba1
        E, N, R = m.inner, m.state, m.dt_rank
        with jax.named_scope("in_proj"):
            u, z = jnp.split(jnp.dot(n, self.param("in_proj", init, (d, 2 * E))), 2, axis=-1)
        with jax.named_scope("conv"):
            u = silu_short_conv(
                u, self.param("conv", nn.initializers.normal(m.taps ** -0.5), (E, m.taps)),
                self.param("conv_bias", nn.initializers.zeros, (E,)))
        with jax.named_scope("x_proj"):
            dt, Bm, Cm = jnp.split(
                jnp.dot(u, self.param("x_proj", init, (E, R + 2 * N))), [R, R + N], axis=-1)
            dt = jnp.dot(dt, self.param("dt_proj", symmetric_uniform(R ** -0.5), (R, E)))
        with jax.named_scope("selective_scan"):
            y = selective_scan(
                u, dt, -jnp.exp(self.param("A_log", a_log_range_init, (E, N)).astype(jnp.float32)),
                Bm, Cm, self.param("D", nn.initializers.ones, (E,)),
                self.param("dt_bias", dt_bias_init(m.dt_min, m.dt_max, m.dt_floor), (E,)))
        with jax.named_scope("gate"):
            out = silu_gate(z, y)
        return out, y

    @nn.nowrap
    def sambay(self, x, carried, init):
        """``x + Mixer(LN(x))``, then ``x + MLP(LN(x))``, of a SambaY layer,
        and what it hands on. A ``gmu`` layer's mixer is the Gated Memory
        Unit ``SiLU(n W_in) * memory``."""
        d = x.shape[-1]
        n = LayerNorm(self.rms_norm_eps, name="input_layernorm")(x)
        if self.kind == "mamba1":
            a, memory = self.mamba_one(n, init)
            if self.keeps:
                carried = dict(carried, memory=memory)
        elif self.kind == "gmu":
            memory = carried["memory"]
            with jax.named_scope("gmu"):
                w_in = self.param("in_proj", init, (d, memory.shape[-1]))
                a = jax.nn.silu(jnp.dot(n, w_in)) * memory
        else:
            a, kv = self.differential(n, carried.get("kv"), init)
            if self.keeps:
                carried = dict(carried, kv=kv)
        with jax.named_scope("out"):
            x = x + jnp.dot(a, self.param("out_proj", init, (a.shape[-1], d)))
        n = LayerNorm(self.rms_norm_eps, name="post_attention_layernorm")(x)
        with jax.named_scope("mlp"):
            gate = jnp.dot(n, self.param("mlp_gate", init, (d, self.ffn)))
            up = jnp.dot(n, self.param("mlp_up", init, (d, self.ffn)))
            x = x + jnp.dot(silu_gate(gate, up), self.param("mlp_down", init, (self.ffn, d)))
        return x, carried

    @nn.nowrap
    def expert_layer(self, n, init):
        """The held routed experts' part plus the shared expert's, and the
        layer's counters. Ungated experts (and their shared expert) have no
        ``experts_gate`` / ``shared_gate`` leaf."""
        d, e = n.shape[-1], self.ffn
        lo, hi = e.held
        weights = [
            self.param("router", init, (d, e.experts)),
            self.param("experts_gate", init, (hi - lo, d, e.width)) if e.gated else None,
            self.param("experts_up", init, (hi - lo, d, e.width)),
            self.param("experts_down", init, (hi - lo, e.width, d)),
            self.param("router_bias", nn.initializers.zeros, (e.experts,)) if e.biased else None,
        ]
        # No jax.checkpoint: what the backward pass reads is bounded by shapes
        # (routed_experts' docstring; 151 MB a layer at 4 096 tokens of 2 304,
        # top-8, 8 of 64 experts of width 896 held), and recomputing it runs
        # three grouped products, the sort and the dispatch gather again.
        y, counters = routed_experts(
            n, *weights, top_k=e.top_k, norm_topk_prob=e.norm_topk_prob, held_from=lo,
            scoring=e.scoring, scale=e.scale, renorm_eps=e.renorm_eps)
        if e.shared_width:
            with jax.named_scope("shared"):
                y = y + (gated_mlp if e.gated else relu2_mlp)(
                    n,
                    *([self.param("shared_gate", init, (d, e.shared_width))] if e.gated else []),
                    self.param("shared_up", init, (d, e.shared_width)),
                    self.param("shared_down", init, (e.shared_width, d)),
                )
        return y, counters

    @nn.nowrap
    def one_part(self, x, init):
        """``x + Part(RMSNorm(x))`` of a layer whose kind is one of
        :data:`PARTS`."""
        B, T, d = x.shape
        n = RMSNorm(self.rms_norm_eps, name="norm")(x)
        if self.kind == "experts":
            y, counters = self.expert_layer(n.reshape(B * T, d), init)
            self.sow("counters", "moe", counters)
            return x + y.reshape(B, T, d)
        if self.kind == "mlp":
            with jax.named_scope("mlp"):
                return x + relu2_mlp(
                    n,
                    self.param("mlp_up", init, (d, self.ffn)),
                    self.param("mlp_down", init, (self.ffn, d)),
                )
        if self.kind == "mamba":
            a, leaf = self.mamba(n, init), "out_proj"
        else:
            a, leaf = self.grouped_query(n, None, None, init).reshape(B, T, -1), "o_proj"
        with jax.named_scope("out"):
            return x + jnp.dot(a, self.param(leaf, init, (a.shape[-1], d)))

    @nn.compact
    def __call__(self, x, cos, sin, carried=None):
        B, T, d = x.shape
        init = nn.initializers.normal(0.02)
        if self.kind in PARTS.values():
            return self.one_part(x, init)
        if self.layer_norm:
            return self.sambay(x, carried, init)
        n = RMSNorm(self.rms_norm_eps, name="input_layernorm")(x)
        if self.kind == "conv":
            a, leaf = self.short_conv(n, init), "out_proj"
        else:
            a = (self.latent if self.attn.rope_dim else self.grouped_query)(n, cos, sin, init)
            a, leaf = a.reshape(B, T, -1), "o_proj"
        with jax.named_scope("out"):
            x = x + jnp.dot(a, self.param(leaf, init, (a.shape[-1], d)))
        n = RMSNorm(self.rms_norm_eps, name="post_attention_layernorm")(x)
        if isinstance(self.ffn, ExpertSpec):
            y, counters = self.expert_layer(n.reshape(B * T, d), init)
            self.sow("counters", "moe", counters)
            return x + y.reshape(B, T, d)
        with jax.named_scope("mlp"):
            return x + gated_mlp(
                n,
                self.param("mlp_gate", init, (d, self.ffn)),
                self.param("mlp_up", init, (d, self.ffn)),
                self.param("mlp_down", init, (self.ffn, d)),
            )


class DecoderLM(nn.Module):
    """Arguments mirror the ``config.json`` keys of the source model (plus
    ``experts_held``), in either family's vocabulary: ``layer_types`` or
    ``num_hidden_layers`` for the depth, ``num_experts`` or
    ``n_routed_experts``, ``rope_parameters`` (per layer kind) or one
    ``rope_theta`` for every layer. The defaults are a small model for the
    CLI and tests, not a published one."""

    vocab_size: int
    hidden_size: int = 128
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 32
    layer_types: Optional[Sequence[str]] = None
    num_hidden_layers: Optional[int] = None
    sliding_window: int = 64
    rope_parameters: Optional[Mapping[str, Any]] = None
    num_experts: int = 8
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 128
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    experts_held: Optional[Sequence[int]] = None
    # query heads of each layer, on num_key_value_heads (HF's laguna)
    num_attention_heads_per_layer: Optional[Sequence[int]] = None
    # latent attention (DeepseekV3Attention's keys)
    kv_lora_rank: Optional[int] = None
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    rope_theta: float = 10000.0
    rope_interleave: bool = False
    rope_scaling: Optional[Mapping[str, Any]] = None
    # leading dense layers, the router's rules and the shared expert
    first_k_dense_replace: int = 0
    intermediate_size: Optional[int] = None
    n_routed_experts: Optional[int] = None
    n_shared_experts: int = 0
    scoring_func: str = "softmax"
    topk_method: str = "greedy"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    renorm_eps: Optional[float] = None
    # conv layers and QK norms
    conv_L_cache: Optional[int] = None
    use_qk_norm: bool = False
    # a one-part stack (HF's nemotron_h keys): the pattern, the Mamba-2
    # mixer, the experts' activation and the shared expert's own width
    hybrid_override_pattern: Optional[str] = None
    mamba_num_heads: Optional[int] = None
    mamba_head_dim: Optional[int] = None
    n_groups: int = 1
    ssm_state_size: Optional[int] = None
    conv_kernel: Optional[int] = None
    chunk_size: int = 128
    use_conv_bias: bool = True
    mamba_hidden_act: str = "silu"
    time_step_limit: Optional[Sequence[Optional[float]]] = None
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    mlp_hidden_act: str = "silu"
    moe_shared_expert_intermediate_size: Optional[int] = None
    # a SambaY stack (HF's phi4flash keys): a Mamba layer every mb_per_layer
    # of num_hidden_layers, of which this chip holds [layers_held[0],
    # layers_held[1]); the Mamba-1 sizes
    mb_per_layer: Optional[int] = None
    layers_held: Optional[Sequence[int]] = None
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2

    def sambay(self) -> bool:
        """Whether the stack is a SambaY decoder-hybrid-decoder (arXiv:2507.06607)."""
        return self.mb_per_layer is not None

    def sambay_layers(self) -> Tuple[Tuple[int, str, bool], ...]:
        """Each held layer of a SambaY stack: its published index ``l``, its
        kind and whether it keeps what later layers read, by the source's
        rules over ``L = num_hidden_layers``: a Mamba layer where ``l %
        mb_per_layer == 0``, else attention; from ``L / 2`` on the
        cross-decoder, where the first Mamba layer keeps its scan output as
        the memory and the first attention layer (full) its keys and values,
        and from ``L / 2 + 2`` on every Mamba layer is a Gated Memory Unit
        over that memory and every attention layer attends over those keys
        (``cross_attention``); before ``L / 2`` attention is windowed."""
        for key in ("layer_types", "hybrid_override_pattern", "kv_lora_rank",
                    "num_attention_heads_per_layer"):
            if getattr(self, key) is not None:
                raise ValueError(f"{key} beside mb_per_layer: a SambaY stack's layers follow "
                                 "from mb_per_layer and layers_held")
        if (self.first_k_dense_replace or self.use_qk_norm or self.intermediate_size is None
                or not self.tie_word_embeddings):
            raise ValueError("a SambaY stack has dense MLPs of intermediate_size in every layer, "
                             "no QK norm and a tied head")
        L, every = int(self.num_hidden_layers or 0), int(self.mb_per_layer)
        lo, hi = self.layers_held or (0, L)
        if every < 1 or not 0 <= lo < hi <= L:
            raise ValueError(f"layers_held {(lo, hi)} within num_hidden_layers {L}, "
                             f"mb_per_layer {every}")
        half = L // 2
        out = []
        for l in range(lo, hi):
            if l % every == 0:
                kind, keeps = ("gmu", False) if l >= half + 2 else ("mamba1", l >= half)
            elif l >= half + 2:
                kind, keeps = "cross_attention", False
            else:
                kind, keeps = ("sliding_attention", False) if l < half else ("full_attention", True)
            out.append((l, kind, keeps))
        kept = {kind for _, kind, keeps in out if keeps}
        for reader, source in (("gmu", "mamba1"), ("cross_attention", "full_attention")):
            if any(kind == reader for _, kind, _ in out) and source not in kept:
                at = half if source == "mamba1" else half + 1
                raise ValueError(
                    f"layers_held {(lo, hi)}: a {reader} layer reads what the {source} layer at "
                    f"{at} keeps, which this chip does not hold")
        return tuple(out)

    def mamba1_spec(self) -> Optional[Mamba1Spec]:
        """The Mamba-1 mixers' and Gated Memory Units' numbers; ``None``
        outside a SambaY stack."""
        if not self.sambay():
            return None
        d = int(self.hidden_size)
        return Mamba1Spec(int(self.mamba_expand) * d, int(self.mamba_d_state),
                          int(self.mamba_d_conv), -(-d // 16), float(self.time_step_min),
                          float(self.time_step_max), float(self.time_step_floor))

    def one_part(self) -> bool:
        """Whether the stack is told by ``hybrid_override_pattern``: every
        layer one part under one norm, attention without rotary, ungated
        ReLU-squared experts and MLPs (what HF's ``nemotron_h`` classes fix)."""
        return self.hybrid_override_pattern is not None

    def kinds(self) -> Tuple[str, ...]:
        """Every layer's kind; the depth is its length."""
        if self.sambay():
            return tuple(kind for _, kind, _ in self.sambay_layers())
        if self.one_part():
            pattern = str(self.hybrid_override_pattern)
            if self.layer_types is not None:
                raise ValueError(
                    "hybrid_override_pattern beside layer_types: a stack is one part a layer "
                    "or a mixer and a feed-forward part a layer, not both")
            if self.first_k_dense_replace:
                raise ValueError(
                    "first_k_dense_replace beside hybrid_override_pattern: a one-part stack "
                    "spells a dense MLP as '-' in its pattern")
            unknown = sorted(set(pattern) - set(PARTS))
            if unknown or not pattern:
                raise ValueError(
                    f"hybrid_override_pattern {pattern!r}: unknown parts {unknown}; "
                    f"have {sorted(PARTS)}")
            if self.num_hidden_layers is not None and int(self.num_hidden_layers) != len(pattern):
                raise ValueError(
                    f"num_hidden_layers {self.num_hidden_layers} is not the length of "
                    f"hybrid_override_pattern {pattern!r}")
            if self.mlp_hidden_act != "relu2":
                raise ValueError(
                    f"mlp_hidden_act {self.mlp_hidden_act!r}: a one-part stack's experts and "
                    "MLPs are ungated ReLU-squared ('relu2' only)")
            return tuple(PARTS[c] for c in pattern)
        if self.mlp_hidden_act != "silu":
            raise ValueError(
                f"mlp_hidden_act {self.mlp_hidden_act!r}: the layers of layer_types have "
                "gated-SiLU MLPs and experts ('silu' only)")
        if self.layer_types is not None:
            kinds = tuple(self.layer_types)
        elif self.num_hidden_layers is not None:
            kinds = ("full_attention",) * int(self.num_hidden_layers)
        else:
            kinds = ("sliding_attention", "full_attention")
        unknown = sorted(set(kinds) - set(LAYER_KINDS))
        if unknown:
            raise ValueError(f"unknown layer kinds {unknown}; have {LAYER_KINDS}")
        return kinds

    def conv_taps(self) -> int:
        """The conv layers' filter length; 0 where no layer is one."""
        if "conv" not in self.kinds():
            return 0
        if not self.conv_L_cache or int(self.conv_L_cache) < 1:
            raise ValueError(
                f"a 'conv' layer needs conv_L_cache, its filter's length, got {self.conv_L_cache}")
        return int(self.conv_L_cache)

    def mamba_spec(self) -> Optional[MambaSpec]:
        """The ``mamba`` layers' numbers; ``None`` where no layer is one."""
        if "mamba" not in self.kinds():
            return None
        sizes = {k: getattr(self, k) for k in (
            "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "conv_kernel")}
        missing = [k for k, v in sizes.items() if not v or int(v) < 1]
        if missing:
            raise ValueError(f"an 'M' layer needs {missing}, got {sizes}")
        if int(self.n_groups) < 1 or int(self.mamba_num_heads) % int(self.n_groups):
            raise ValueError(
                f"n_groups {self.n_groups} does not divide mamba_num_heads {self.mamba_num_heads}")
        if self.mamba_hidden_act != "silu":
            raise ValueError(
                f"mamba_hidden_act {self.mamba_hidden_act!r}: the convolution and the gate "
                "are SiLU here ('silu' only)")
        limit = tuple(self.time_step_limit or (0.0, None))
        if len(limit) != 2 or float(limit[0] or 0.0) > 0 or (
                limit[1] is not None and math.isfinite(float(limit[1]))):
            raise ValueError(
                f"time_step_limit {limit}: a clamp of the step is not expressed here "
                "([0, null] only)")
        return MambaSpec(
            int(self.mamba_num_heads), int(self.mamba_head_dim), int(self.n_groups),
            int(self.ssm_state_size), int(self.conv_kernel), int(self.chunk_size),
            bool(self.use_conv_bias), float(self.time_step_min), float(self.time_step_max),
            float(self.time_step_floor))

    def experts(self) -> int:
        return int(self.num_experts if self.n_routed_experts is None else self.n_routed_experts)

    def held(self):
        lo, hi = self.experts_held or (0, self.experts())
        if not 0 <= lo < hi <= self.experts():
            raise ValueError(f"experts_held {(lo, hi)} is no range within {self.experts()} experts")
        return int(lo), int(hi)

    def attention_spec(self) -> AttentionSpec:
        if self.q_lora_rank is not None:
            raise ValueError(
                f"q_lora_rank {self.q_lora_rank}: a query latent is not expressed here (null only)")
        if self.kv_lora_rank is None:
            if self.num_attention_heads % self.num_key_value_heads:
                raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
            if self.sambay() and (self.num_attention_heads % 2 or self.num_key_value_heads % 2):
                raise ValueError("differential attention halves the query and key/value heads")
            return AttentionSpec(self.num_attention_heads, self.num_key_value_heads, self.head_dim,
                                 qk_norm=bool(self.use_qk_norm),
                                 rotary=not (self.one_part() or self.sambay()),
                                 differential=self.sambay())
        if self.one_part():
            raise ValueError(
                "kv_lora_rank beside hybrid_override_pattern: a one-part stack's attention "
                "is grouped-query without rotary")
        if self.use_qk_norm:
            raise ValueError("use_qk_norm beside a latent (kv_lora_rank) is not expressed here")
        if self.rope_scaling is not None:
            raise ValueError("rope_scaling beside a latent (kv_lora_rank) is not expressed here")
        if any(float(rope.get("partial_rotary_factor", 1.0)) != 1.0
               for rope in (self.rope_parameters or {}).values() if isinstance(rope, Mapping)):
            raise ValueError(
                "partial_rotary_factor beside a latent (kv_lora_rank) is not expressed here: "
                "its rope dims are qk_rope_head_dim")
        widths = (self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim)
        if None in widths or self.qk_rope_head_dim % 2:
            raise ValueError(
                "latent attention needs qk_nope_head_dim, an even qk_rope_head_dim and "
                f"v_head_dim, got {widths}")
        return AttentionSpec(
            self.num_attention_heads, self.num_attention_heads, int(self.qk_nope_head_dim),
            int(self.qk_rope_head_dim), int(self.v_head_dim), int(self.kv_lora_rank),
            bool(self.rope_interleave))

    def rope_by_kind(self) -> Mapping[str, Mapping[str, Any]]:
        """Each layer kind's rotary parameters: ``rope_parameters[kind]``
        (a ``yarn`` kind without its own ``original_max_position_embeddings``
        takes the one beside the kinds), or the default type at
        ``rope_theta`` for every kind."""
        if self.rope_parameters is None:
            return {kind: {"rope_type": "default", "rope_theta": self.rope_theta}
                    for kind in LAYER_KINDS}
        around = {k: v for k, v in self.rope_parameters.items() if k not in LAYER_KINDS}
        unknown = sorted(set(around) - {"original_max_position_embeddings"})
        if unknown:
            raise ValueError(
                f"rope_parameters: unknown keys {unknown} beside the layer kinds {LAYER_KINDS}")
        out = {}
        for kind in LAYER_KINDS:
            if kind not in self.rope_parameters:
                continue
            rope = dict(self.rope_parameters[kind])
            unknown = sorted(set(rope) - set(ROPE_KEYS))
            if unknown:
                raise ValueError(f"rope_parameters[{kind!r}]: unknown keys {unknown}; have {ROPE_KEYS}")
            if "original_max_position_embeddings" in around:
                rope.setdefault("original_max_position_embeddings",
                                around["original_max_position_embeddings"])
            out[kind] = rope
        return out

    def attention_specs(self) -> Tuple[AttentionSpec, ...]:
        """Every layer's attention numbers (whatever its kind):
        :meth:`attention_spec` with the layer's own query heads where
        ``num_attention_heads_per_layer`` gives them, and the dims its kind
        turns where that is not the whole head."""
        base = self.attention_spec()
        kinds = self.kinds()
        heads = self.num_attention_heads_per_layer
        if heads is not None:
            if base.rope_dim or self.one_part():
                raise ValueError(
                    "num_attention_heads_per_layer beside a latent (kv_lora_rank) or "
                    "hybrid_override_pattern is not expressed here")
            heads = tuple(int(h) for h in heads)
            if len(heads) != len(kinds) or any(h < 1 or h % base.kv_heads for h in heads):
                raise ValueError(
                    f"num_attention_heads_per_layer {heads}: one count a layer ({len(kinds)}), "
                    f"each a multiple of num_key_value_heads {base.kv_heads}")
        turned = {}
        if not base.rope_dim and base.rotary:
            turned = {kind: rotated_dims(rope, base.head_dim)
                      for kind, rope in self.rope_by_kind().items()}
        return tuple(
            dataclasses.replace(
                base, heads=heads[i] if heads else base.heads,
                rotary_dim=0 if turned.get(kind, base.head_dim) == base.head_dim else turned[kind])
            for i, kind in enumerate(kinds))

    def attention_constants(self, length: int) -> dict:
        """The ``flush`` span's constants of a stack whose grouped-query
        attention layers are of more than one shape, which
        ``attention.gqa_core_peak_pct`` reads: the trained ``attn_length``,
        ``attn_window``, and per kind (``full``, ``sliding``) its layers,
        query heads, key/value heads, head dim and turned dims
        (``attn_full_layers``, ``attn_full_heads``, ``attn_full_kv_heads``,
        ``attn_full_head_dim``, ``attn_full_rotary_dim``, ...). Empty where
        every attention layer has one shape (every accepted configuration
        but one), and where the layers of one kind differ among themselves."""
        by_kind = {}
        for kind, spec in zip(self.kinds(), self.attention_specs()):
            if kind in ("full_attention", "sliding_attention"):
                by_kind.setdefault(kind, set()).add(spec)
        if len({spec.site() for specs in by_kind.values() for spec in specs}) < 2 or any(
                len(specs) > 1 for specs in by_kind.values()):
            return {}
        out = {"attn_length": int(length), "attn_window": int(self.sliding_window)}
        kinds = self.kinds()
        for kind, (spec,) in by_kind.items():
            name = kind.split("_")[0]
            out.update({
                f"attn_{name}_layers": kinds.count(kind), f"attn_{name}_heads": spec.heads,
                f"attn_{name}_kv_heads": spec.kv_heads, f"attn_{name}_head_dim": spec.head_dim,
                f"attn_{name}_rotary_dim": spec.rotary_dim or spec.head_dim,
            })
        return out

    def expert_spec(self) -> ExpertSpec:
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError(
                f"n_group {self.n_group}, topk_group {self.topk_group}: group-limited routing "
                "is not expressed here (1 only)")
        if self.scoring_func not in SCORING:
            raise ValueError(f"unknown scoring_func {self.scoring_func!r}; have {SCORING}")
        if self.topk_method not in ("greedy", "noaux_tc"):
            raise ValueError(
                f"unknown topk_method {self.topk_method!r}; have 'greedy' and 'noaux_tc'")
        eps = self.renorm_eps
        if eps is None:
            # HF's DeepseekV3 router adds 1e-20 to the sum it renormalises by
            eps = 1e-20 if self.scoring_func == "sigmoid" else 0.0
        shared = int(self.n_shared_experts) * int(self.moe_intermediate_size)
        if self.moe_shared_expert_intermediate_size is not None:
            # one shared expert at a width of its own (HF's nemotron_h)
            shared = int(self.moe_shared_expert_intermediate_size) if self.n_shared_experts else 0
        return ExpertSpec(
            self.experts(), int(self.num_experts_per_tok), int(self.moe_intermediate_size),
            self.held(), bool(self.norm_topk_prob), self.scoring_func,
            self.topk_method == "noaux_tc", float(self.routed_scaling_factor),
            shared, float(eps), gated=not self.one_part())

    def feed_forwards(self):
        """Every layer's ``DecoderLayer.ffn``: the dense width in the leading
        ``first_k_dense_replace`` layers, the expert layer's numbers after; in
        a one-part stack the expert layer's numbers in an ``E`` layer, the
        dense width in a ``-`` layer and ``None`` in a mixer's."""
        if self.sambay():
            return (int(self.intermediate_size),) * len(self.kinds())
        if self.one_part():
            kinds = self.kinds()
            if "mlp" in kinds and self.intermediate_size is None:
                raise ValueError("a '-' layer needs intermediate_size, the dense MLP's width")
            by_kind = {"mlp": int(self.intermediate_size or 0)}
            if "experts" in kinds:
                by_kind["experts"] = self.expert_spec()
            return tuple(by_kind.get(kind) for kind in kinds)
        dense = min(int(self.first_k_dense_replace), len(self.kinds()))
        if dense and self.intermediate_size is None:
            raise ValueError("first_k_dense_replace needs intermediate_size, the dense MLP's width")
        return (int(self.intermediate_size or 0),) * dense + (
            self.expert_spec(),) * (len(self.kinds()) - dense)

    def attention_sites(self) -> Tuple[Tuple[int, ...], ...]:
        """The calls of ``ops/attention.attention`` in a forward pass: a
        layer that has attention gives its own (a ``conv`` layer calls no
        attention), one or a differential layer's two."""
        return tuple(site for kind, spec in zip(self.kinds(), self.attention_specs())
                     if kind in ATTENTION_KINDS for site in spec.sites())

    def attention_layers(self) -> int:
        return sum(kind in ATTENTION_KINDS for kind in self.kinds())

    def rope_sites(self) -> Tuple[Tuple[int, ...], ...]:
        """The attention layers' calls of the rotate-half operator
        (``AttentionSpec.rope_sites``)."""
        return tuple(site for kind, spec in zip(self.kinds(), self.attention_specs())
                     if kind in ATTENTION_KINDS for site in spec.rope_sites())

    def grouped_sites(self, tokens: int) -> Tuple[Tuple[int, int, int, int], ...]:
        """The expert layers' grouped products that a forward pass over
        ``tokens`` runs outside the overflow loops (``_held_rows``), each with
        two more in the backward pass."""
        return tuple(site for ffn in self.feed_forwards() if isinstance(ffn, ExpertSpec)
                     for site in ffn.grouped_sites(self.hidden_size, tokens))

    def slot_sites(self, tokens: int) -> Tuple[Tuple[int, int, int, int], ...]:
        """The expert layers' token-side sums in a step of ``tokens`` (the
        forward of ``weighted_rows``, the backward of ``take_rows``)."""
        return tuple(site for ffn in self.feed_forwards() if isinstance(ffn, ExpertSpec)
                     for site in ffn.slot_sites(self.hidden_size, tokens))

    def flush_attrs(self, length: int, batch: int) -> dict:
        """``ModelDef.flush_attrs`` for a step of ``batch`` sequences of
        ``length``: of each kernel-backed op, the calls that take its kernel
        (by the op's own ``takes_kernel``) and all of them; the numbers the
        per-layer metrics' FLOPs and bytes follow from: latent attention's
        widths, the expert layers' (``layers`` is what their counters are
        summed over, ``expert_products`` the grouped products a held pair
        runs forward), the conv layers', the state-space layers' ``ssm_*``
        and ``attention_constants``."""
        out = {}
        kinds, tokens = self.kinds(), batch * length
        sites = self.attention_sites()
        if sites:
            out.update(attn_kernel_sites=sum(attention_op.takes_kernel(length, *site)
                                             for site in sites),
                       attn_sites=len(sites))
        rope = self.rope_sites()
        if rope:
            out.update(rope_kernel_sites=sum(rotary_op.takes_kernel(length, *site)
                                             for site in rope),
                       rope_sites=len(rope))
        attn = self.attention_spec()
        if sites and attn.rope_dim:
            # what latent attention's core FLOPs follow from
            out.update(attn_qk_width=attn.head_dim + attn.rope_dim, attn_v_width=attn.v_dim,
                       attn_heads=attn.heads, attn_length=int(length), attn_layers=len(sites))
        routed = [f for f in self.feed_forwards() if isinstance(f, ExpertSpec)]
        if routed:
            # three grouped products a site (its forward and two gradients),
            # which take one path
            grouped, slots = self.grouped_sites(tokens), self.slot_sites(tokens)
            out.update(
                moe_kernel_sites=3 * sum(grouped_matmul.takes_kernel(*s) for s in grouped),
                moe_grouped_sites=3 * len(grouped),
                moe_slot_kernel_sites=sum(slot_sum.takes_kernel(*s) for s in slots),
                moe_slot_sites=len(slots),
                hidden=self.hidden_size, expert_width=routed[0].width, layers=len(routed),
                expert_layers=len(routed), top_k=routed[0].top_k,
                expert_products=routed[0].products())
            if routed[0].shared_width:
                out["shared_width"] = routed[0].shared_width
        if self.conv_taps():
            out.update(conv_layers=kinds.count("conv"), conv_width=self.hidden_size)
        out.update(self.attention_constants(length))
        ssm = self.mamba_spec()
        if ssm is not None:
            # one scan a state-space layer
            layers = kinds.count("mamba")
            takes = ssd_op.takes_kernel(length, ssm.heads, ssm.head_dim, ssm.groups,
                                        ssm.state, ssm.chunk)
            out.update(ssd_kernel_sites=layers * takes, ssd_sites=layers, ssm_layers=layers,
                       ssm_heads=ssm.heads, ssm_head_dim=ssm.head_dim, ssm_state=ssm.state,
                       ssm_groups=ssm.groups, ssm_chunk=ssm.chunk)
        if self.sambay():
            out.update(self.sambay_constants(length))
        return out

    def sambay_constants(self, length: int) -> dict:
        """A SambaY stack's ``flush`` constants: of its selective scans (one
        a Mamba-1 layer and local step) the calls that take the kernels and
        all of them, the layers, channels and state (``sscan_*``); of its
        differential attention the trained length, the window, the layers
        of each kind, the pairs of query heads, the key/value groups, the
        keys' and the values' widths (``diff_*``)."""
        kinds, out = self.kinds(), {}
        m, attn = self.mamba1_spec(), self.attention_spec()
        scans = kinds.count("mamba1")
        if scans:
            out.update(sscan_kernel_sites=scans * sscan_op.takes_kernel(length, m.inner, m.state),
                       sscan_sites=scans, sscan_layers=scans, sscan_inner=m.inner,
                       sscan_state=m.state)
        if any(kind in ATTENTION_KINDS for kind in kinds):
            out.update(diff_length=int(length), diff_window=int(self.sliding_window),
                       **{f"diff_{kind.split('_')[0]}_layers": kinds.count(kind)
                          for kind in ("sliding_attention", "full_attention", "cross_attention")},
                       diff_pairs=attn.heads // 2, diff_kv_groups=attn.kv_heads // 2,
                       diff_key_dim=attn.head_dim, diff_value_dim=2 * attn.head_dim)
        return out

    @nn.nowrap
    def sambay_stack(self, tokens):
        """The logits of a SambaY stack: the held layers in order, each
        handed what the earlier ones keep (:meth:`DecoderLayer.sambay`),
        a final LayerNorm and the tied head."""
        embed = nn.Embed(self.vocab_size, self.hidden_size, name="embed_tokens",
                         embedding_init=self.embedding_init())
        x, carried = embed(tokens), {}
        m, ffns = self.mamba1_spec(), self.feed_forwards()
        layers = zip(self.sambay_layers(), self.attention_specs())
        for i, ((l, kind, keeps), spec) in enumerate(layers):
            x, carried = DecoderLayer(
                kind, spec, ffns[i], self.sliding_window, self.layer_norm_eps, mamba1=m,
                layer_norm=True, lambda_init=0.8 - 0.6 * math.exp(-0.3 * l), keeps=keeps,
                name=f"layers_{i}",
            )(x, None, None, carried)
        x = LayerNorm(self.layer_norm_eps, name="norm")(x)
        return embed.attend(x)

    @nn.nowrap
    def embedding_init(self):
        """A tied table at the sources' scale (normal 0.02: the logits start
        under 1); an untied one at unit RMS: under an RMS norm a 0.02
        embedding is drowned by the attention branch's mean over the
        context, which every position shares, and a fresh router collapses
        onto a few experts."""
        return nn.initializers.normal(0.02 if self.tie_word_embeddings else 1.0)

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        if self.sambay():
            return self.sambay_stack(tokens)
        B, T = tokens.shape
        kinds, specs, taps = self.kinds(), self.attention_specs(), self.conv_taps()
        attn, ssm = self.attention_spec(), self.mamba_spec()
        # one pair of tables a kind of attention; a conv layer has no positions,
        # and no layer of a one-part stack
        turning = [kind for kind in dict.fromkeys(kinds) if kind in LAYER_KINDS and kind != "conv"]
        with jax.named_scope("rope"):
            if attn.rope_dim:
                cos, sin = rotary_tables(
                    {"rope_type": "default", "rope_theta": self.rope_theta}, attn.rope_dim, T)
                if attn.interleave:
                    # each angle twice in a row: the pairs are (2m, 2m + 1)
                    half = attn.rope_dim // 2
                    cos, sin = (jnp.repeat(t[:, :half], 2, axis=-1) for t in (cos, sin))
                tables = {kind: (cos, sin) for kind in turning}
            else:
                rope = self.rope_by_kind()
                tables = {kind: rotary_tables(rope[kind], self.head_dim, T) for kind in turning}
        embed = nn.Embed(self.vocab_size, self.hidden_size, name="embed_tokens",
                         embedding_init=self.embedding_init())
        x = embed(tokens)
        for i, (kind, spec, ffn) in enumerate(zip(kinds, specs, self.feed_forwards())):
            x = DecoderLayer(
                kind, spec, ffn, self.sliding_window, self.rms_norm_eps, taps, ssm,
                name=f"layers_{i}",
            )(x, *tables.get(kind, (None, None)))
        x = RMSNorm(self.rms_norm_eps, name="norm")(x)
        if self.tie_word_embeddings:
            return embed.attend(x)
        return nn.Dense(self.vocab_size, use_bias=False, name="lm_head",
                        kernel_init=nn.initializers.normal(0.02))(x)
