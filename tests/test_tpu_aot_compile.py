"""The main path's programs COMPILE for the real chip — checked here, on
the CPU, with no chip attached.

The TPU compiler ships with the installation and compiles for a chip that
is *described* (``jax.experimental.topologies``), which shows what
interpret mode cannot: a slice not aligned to the tiling, a kernel asking
for more VMEM than it may use, a program that does not fit one chip's
HBM. Nothing runs, so these tests say nothing about results or times —
``chip_smoke.py`` is the run. Each case takes about two seconds; the
shapes are the ones the chip smoke and the north-star cells use.

The persistent compilation cache is switched off around the compiles: a
TPU executable written to it cannot be read back without a chip (jax
would warn and recompile on the next run)."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache as jax_cc
from jax.sharding import SingleDeviceSharding

from fedml_tpu.compile import install_hardened_cache


@pytest.fixture(scope="module")
def one_chip():
    """SingleDeviceSharding on one chip of a described v5e 2x2 host."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _compilation_cache_off():
    jax.config.update("jax_enable_compilation_cache", False)
    jax_cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    jax_cc.reset_cache()
    install_hardened_cache()  # re-bind conftest.py's session store


def _on(sharding, tree):
    """Arrays / shape structs -> ShapeDtypeStructs placed on the chip."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


@pytest.mark.parametrize(
    "C,D",
    [(10, 1_690_046), (8, 200), (5, 62), (16, 4096)],
    ids=lambda v: str(v),
)
def test_robust_stats_kernel_compiles_for_v5e(one_chip, C, D):
    """ops/robust_stats rank-selection kernel, interpret=False, at the
    FEMNIST-CNN parameter count and at the small/ragged widths the
    aggregators also see (a bias vector of 62, a 200-wide leaf)."""
    from fedml_tpu.ops.robust_stats import _BLOCK_D, _trimmed_mean_2d

    x = jax.ShapeDtypeStruct((C, D), jnp.float32, sharding=one_chip)
    compiled = _trimmed_mean_2d.lower(
        x, trim_k=1, block_d=min(_BLOCK_D, max(128, D)), interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "clients,shape,window,dtype",
    [
        # (B, T, H, KV, D) a client; the first two are the language-model
        # cells' training steps (chip_smoke.py's kernel check runs them)
        (4, (4, 1024, 12, 12, 64), None, jnp.bfloat16),    # gpt2-124m.silo4, vmap
        (1, (2, 2048, 32, 4, 128), 1024, jnp.bfloat16),    # mellum2-12b-a2.5b.silo2
        # lfm2-8b-a1b.silo2t4k: MAX_LENGTH, two heads of 64 a tile sharing a
        # K/V head. Outside tier 1 (102 s here beside five busy workers, more
        # than the rest of this file together): run it with ``-m slow`` before
        # chip time goes on this shape; the cell compiles it on the chip.
        pytest.param(1, (1, 4096, 32, 8, 64), None, jnp.bfloat16, marks=pytest.mark.slow),
        # laguna-xs.2.silo2t4k-swa: six query heads a K/V head on its full
        # layers, eight under the window of 512 on its sliding ones (slow too)
        pytest.param(1, (1, 4096, 48, 8, 128), None, jnp.bfloat16, marks=pytest.mark.slow),
        pytest.param(1, (1, 4096, 64, 8, 128), 512, jnp.bfloat16, marks=pytest.mark.slow),
        (1, (8, 4096, 1, 1, 64), None, jnp.bfloat16),      # a head a batch row
        (1, (1, 2048, 8, 2, 128), 512, jnp.float32),
    ],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else getattr(v, "__name__", str(v)),
)
def test_flash_attention_fwd_bwd_compiles_for_v5e(one_chip, clients, shape, window, dtype):
    """ops/flash_attention forward and backward kernels, compiled."""
    from fedml_tpu.ops import flash_attention_bthd

    B, T, H, KV, D = shape

    def loss(q, k, v):
        out = jax.vmap(lambda q, k, v: flash_attention_bthd(
            q, k, v, causal=True, window=window, interpret=False))(q, k, v)
        return jnp.sum(out.astype(jnp.float32))

    qkv = [jax.ShapeDtypeStruct((clients, B, T, heads, D), dtype, sharding=one_chip)
           for heads in (H, KV, KV)]
    compiled = (
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        .lower(*qkv)
        .compile()
    )
    # one custom call forward, one backward (dQ, dK and dV together)
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_latent_attention_fwd_bwd_compiles_for_v5e(one_chip):
    """The two-term form of both kernels at kanana-2-30b-a3b.silo2b1's
    training step: 32 heads of 128 | 64 with values of 128 over 2 048
    positions, the one rotary key shared (chip_smoke.py's kernel check runs
    it)."""
    from fedml_tpu.ops.attention import attention, takes_kernel

    B, T, H, D, R = 1, 2048, 32, 128, 64
    assert takes_kernel(T, H, H, D, R, D)

    def loss(q, k, v, q_rope, k_rope):
        out = attention(q, k, v, causal=True, q_rope=q_rope, k_rope=k_rope,
                        scale=(D + R) ** -0.5)
        return jnp.sum(out.astype(jnp.float32))

    shapes = [(B, T, H, D)] * 3 + [(B, T, H, R), (B, T, 1, R)]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip) for s in shapes]
    import importlib

    module = importlib.import_module("fedml_tpu.ops.flash_attention")
    saved, module._use_interpret = module._use_interpret, lambda: False
    try:
        compiled = jax.jit(jax.value_and_grad(loss, argnums=range(5))).lower(*args).compile()
    finally:
        module._use_interpret = saved
    assert compiled.as_text().count("tpu_custom_call") >= 2


@pytest.mark.parametrize("clients,shape,turned,dtype", [
    (1, (2, 2048, 32, 128), 128, jnp.bfloat16),  # mellum2-12b-a2.5b.silo2's q: a head a lane tile
    (1, (2, 2048, 4, 128), 128, jnp.bfloat16),   # and its k
    (1, (1, 4096, 32, 64), 64, jnp.bfloat16),    # lfm2-8b-a1b.silo2t4k's q: two heads a tile
    (2, (1, 4096, 8, 64), 64, jnp.bfloat16),     # its k, under a client vmap
    (1, (1, 512, 4, 32), 32, jnp.float32),       # four heads a tile, float32
    (1, (1, 4096, 48, 128), 64, jnp.bfloat16),   # laguna-xs.2.silo2t4k-swa's full q: half turned
    (2, (1, 4096, 8, 128), 64, jnp.bfloat16),    # and its k, under a client vmap
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else getattr(v, "__name__", str(v)))
def test_rotary_fwd_bwd_compiles_for_v5e(one_chip, clients, shape, turned, dtype):
    """ops/rotary's kernel, forward and backward (the lane rotations, the
    select where heads share a tile or half a head turns, blocks within the
    scoped VMEM), at the three cells' q and k (chip_smoke.py's kernel check
    runs the first four)."""
    from fedml_tpu.ops import rotary as op

    B, T, H, D = shape
    assert op.takes_kernel(T, H, D, turned)

    def loss(x, cos, sin):
        out = jax.vmap(lambda x: op.rotary(x, cos, sin))(x)
        return jnp.sum(out.astype(jnp.float32))

    args = [jax.ShapeDtypeStruct((clients,) + shape, dtype, sharding=one_chip)] + [
        jax.ShapeDtypeStruct((T, turned), jnp.float32, sharding=one_chip)] * 2
    saved, op._use_interpret = op._use_interpret, lambda: False
    try:
        text = jax.jit(jax.value_and_grad(loss)).lower(*args).compile().as_text()
    finally:
        op._use_interpret = saved
    assert "rotary_fwd" in text and "rotary_bwd" in text and text.count("tpu_custom_call") >= 2


def test_expert_layer_at_mellum_share_compiles_for_v5e_over_bounded_rows(one_chip):
    """models/decoder.routed_experts as DecoderLayer calls it, forward +
    gradient at mellum2-12b-a2.5b.silo2's training step: 4 096 tokens of
    width 2 304, top-8 of 64 experts with 8 held. The arrays between the
    sort and the sum have the bound's 8 192 rows, not the 32 768 (token,
    slot) rows; the overflow's loop is in the program once forward and once
    backward; and the pass every step runs keeps its rows, so nine grouped
    kernels lie outside the loops (twelve with the forward run again): since
    PR 39 ``ops/grouped_matmul``'s ``gmm_fwd``, ``gmm_dx`` and ``gmm_dw``,
    and no ``ragged_dot`` is left. The token side's two sums outside the
    loops are ``ops/slot_sum``'s kernel."""
    import re

    from fedml_tpu.models.decoder import routed_experts, row_bound
    from fedml_tpu.ops import grouped_matmul as op
    from fedml_tpu.ops import slot_sum

    N, d, f, held, experts, top_k = 4096, 2304, 896, 8, 64, 8
    assert row_bound(N * top_k, held, experts) == 8192

    def loss(*args):
        y, counters = routed_experts(*args, top_k=top_k)
        return jnp.sum(y.astype(jnp.float32) ** 2), counters

    shapes = [(N, d), (d, experts), (held, d, f), (held, d, f), (held, f, d)]
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip) for s in shapes]
    # the jitted ``_held_rows`` keeps its trace: one from an interpreted run
    # at these shapes (tests/test_decoder.py makes one) must not be compiled
    # for the chip, nor this one run on the CPU after it
    jax.clear_caches()
    saved = op._use_interpret, slot_sum._use_interpret
    op._use_interpret = slot_sum._use_interpret = lambda: False
    try:
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)).lower(
            *args).compile()
    finally:
        op._use_interpret, slot_sum._use_interpret = saved
        jax.clear_caches()
    text = compiled.as_text()
    assert "[8192,2304]" in text and "[32768,2304]" not in text
    assert text.count(" while(") == 2 and "ragged-dot" not in text
    entry = text[text.index("\nENTRY "):]
    kernels = re.findall(r'custom_call_target="tpu_custom_call".*op_name="[^"]*/(gmm_\w+)/', entry)
    assert sorted(kernels) == ["gmm_dw"] * 3 + ["gmm_dx"] * 3 + ["gmm_fwd"] * 3
    # the weighted sum forward and the dispatch gather's backward
    assert len(re.findall(r'custom_call_target="tpu_custom_call".*op_name="[^"]*/slot_sum/',
                          entry)) == 2
    # the full-row program planned 0.547 GiB for this layer, the bounded rows
    # 0.33, kept or recomputed (one layer alone holds its rows either way);
    # 0.24 with the kernels, which copy no weights transposed
    assert compiled.memory_analysis().temp_size_in_bytes < 0.45 * 2**30


# cell -> (tokens, top_k, width, held experts, experts) of a training step;
# evaluation routes 16 384 tokens
SLOT_CELLS = {
    "mellum2-12b-a2.5b.silo2": (4096, 8, 2304, 8, 64),
    "lfm2-8b-a1b.silo2t4k": (4096, 4, 2048, 8, 32),
    "kanana-2-30b-a3b.silo2b1": (2048, 6, 2048, 8, 128),
    "nemotron-twotower-30b-a3b.silo2t4k-ssm": (4096, 6, 2688, 8, 128),
    "laguna-xs.2.silo2t4k-swa": (4096, 8, 2048, 16, 256),
}


@pytest.mark.parametrize("cell", sorted(SLOT_CELLS))
def test_slot_sum_compiles_for_v5e_at_the_expert_cells_shapes(one_chip, cell):
    """``ops/slot_sum``'s kernel, weighted and not, over a bfloat16 table of
    the bound's rows at each expert cell's training step: the table in VMEM,
    its rows loaded one at a time, the readers and weights in SMEM; at
    ``lfm2-8b-a1b.silo2t4k``'s shape too, which the rule sends to
    ``sum_readers`` (4 slots a token, the table's rows half its slots). The
    evaluation's tables (16 384 tokens) outgrow the kernel's VMEM and keep
    ``sum_readers``."""
    from fedml_tpu.models.decoder import row_bound
    from fedml_tpu.ops import slot_sum as op

    N, top_k, d, held, experts = SLOT_CELLS[cell]
    R = row_bound(N * top_k, held, experts)
    assert op.takes_kernel(N, top_k, d, R) is (top_k >= 6)
    assert not op.takes_kernel(16384, top_k, d, row_bound(16384 * top_k, held, experts))
    table = jax.ShapeDtypeStruct((R, d), jnp.bfloat16, sharding=one_chip)
    readers = jax.ShapeDtypeStruct((N, top_k), jnp.int32, sharding=one_chip)
    weights = jax.ShapeDtypeStruct((N, top_k), jnp.float32, sharding=one_chip)
    saved, op._use_interpret = op._use_interpret, lambda: False
    try:
        for args in ((table, readers, weights), (table, readers)):
            text = jax.jit(op.slot_sum).lower(*args).compile().as_text()
            assert text.count("tpu_custom_call") == 1 and " while(" not in text
    finally:
        op._use_interpret = saved


def _scan_compiled(one_chip, clients):
    """``ops/ssd.ssd`` forward + gradient at nemotron-twotower-30b-a3b
    .silo2t4k-ssm's training step (one document of 4 096 positions, 64 heads
    of 64, 8 groups, state 128, chunks of 128), for ``clients`` under a
    client vmap or none, compiled with the kernels."""
    from fedml_tpu.ops import ssd as op

    T, H, P, G, N = 4096, 64, 64, 8, 128
    assert op.takes_kernel(T, H, P, G, N, 128)

    def loss(x, dt, A, B, C, D):
        scan = lambda *a: op.ssd(*a, 128)
        if clients:
            scan = jax.vmap(scan)
        return jnp.sum(scan(x, dt, A, B, C, D).astype(jnp.float32))

    lead = (clients,) if clients else ()
    shapes = [((1, T, H, P), jnp.bfloat16), ((1, T, H), jnp.float32), ((H,), jnp.float32),
              ((1, T, G, N), jnp.bfloat16), ((1, T, G, N), jnp.bfloat16), ((H,), jnp.float32)]
    args = [jax.ShapeDtypeStruct(lead + s, d, sharding=one_chip) for s, d in shapes]
    saved, op._use_interpret = op._use_interpret, lambda: False
    try:
        return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)))).lower(*args).compile()
    finally:
        op._use_interpret = saved


def test_state_space_scan_fwd_bwd_compiles_for_v5e_within_its_plan(one_chip):
    """The scan goes to its two kernels (``ssd_fwd``, ``ssd_bwd``: the
    broadcasts, transposes and lane selects, and ``ssd_bwd``'s 8 MiB of chunk
    states within the VMEM it asks for), and its plan is the kernels' blocks
    and the small float32 arrays of the decays around them: 0.04 GiB, where
    the chunked products that XLA lowers (decay matrix [32, 64, 128, 128]
    float32, 134 MB a layer) planned 0.30 GiB for autodiff of their recomputed
    forward. About 2 s."""
    compiled = _scan_compiled(one_chip, 0)
    text = compiled.as_text()
    assert "ssd_fwd" in text and "ssd_bwd" in text and text.count("tpu_custom_call") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 0.1 * 2**30


def test_state_space_scan_kernels_compile_for_v5e_under_a_client_vmap(one_chip):
    """The same under the ``vmap`` client schedule, each client with its own
    A and D: the batched kernels take the clients as a grid axis of their own."""
    compiled = _scan_compiled(one_chip, 2)
    text = compiled.as_text()
    assert "ssd_fwd" in text and "ssd_bwd" in text and text.count("tpu_custom_call") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2 * 2**30


def _selective_scan_compiled(one_chip, clients):
    """``ops/selective_scan.selective_scan`` forward + gradient at
    phi-4-mini-flash-reasoning.silo2t4k-sambay's training step (one document
    of 4 096 positions, 5 120 channels, state 16), for ``clients`` under a
    client vmap (each with its own A, D and step bias) or none, compiled with
    the kernels."""
    from fedml_tpu.ops import selective_scan as op

    T, C, N = 4096, 5120, 16
    assert op.takes_kernel(T, C, N)

    def loss(*operands):
        scan = jax.vmap(op.selective_scan) if clients else op.selective_scan
        return jnp.sum(scan(*operands).astype(jnp.float32))

    lead = (clients,) if clients else ()
    shapes = [((1, T, C), jnp.bfloat16), ((1, T, C), jnp.bfloat16), ((C, N), jnp.float32),
              ((1, T, N), jnp.bfloat16), ((1, T, N), jnp.bfloat16), ((C,), jnp.float32),
              ((C,), jnp.float32)]
    args = [jax.ShapeDtypeStruct(lead + s, d, sharding=one_chip) for s, d in shapes]
    saved, op._use_interpret = op._use_interpret, lambda: False
    try:
        return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(7)))).lower(*args).compile()
    finally:
        op._use_interpret = saved


@pytest.mark.parametrize("clients", [0, 2], ids=["one_client", "client_vmap"])
def test_selective_scan_kernels_compile_for_v5e_within_their_plan(one_chip, clients):
    """The Mamba-1 scan goes to its two kernels (``sscan_fwd``, ``sscan_bwd``:
    the per-position columns of B and C, the row picks and lane picks, the
    backward's 4.2 MiB of recomputed chunk states within the VMEM it asks
    for), and its plan outside them is the small regrouped B and C and the
    clients' sums: under 0.2 GiB, where the plain form's autodiff would keep
    a [T, C, N] float32 state a block. About 3 s each."""
    compiled = _selective_scan_compiled(one_chip, clients)
    text = compiled.as_text()
    assert "sscan_fwd" in text and "sscan_bwd" in text and text.count("tpu_custom_call") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2 * 2**30


@pytest.mark.parametrize("T,window", [
    (1024, None), (1024, 512),
    # the cell's own length: 15-30 s each here, outside tier 1; the cell
    # compiles them on the chip
    pytest.param(4096, None, marks=pytest.mark.slow),
    pytest.param(4096, 512, marks=pytest.mark.slow),
], ids=["full_1024", "window_1024", "full_4096", "window_4096"])
def test_differential_map_with_values_twice_the_keys_compiles_for_v5e(one_chip, T, window):
    """One differential map as phi-4-mini-flash-reasoning's layers hand it
    to the kernel: 20 query heads on 10 key/value heads of 64 with values of
    128, forward and gradient: the keys padded to the values' width, one
    custom call each way."""
    from fedml_tpu.ops import flash_attention_bthd

    def loss(q, k, v):
        return jnp.sum(flash_attention_bthd(
            q, k, v, causal=True, window=window, interpret=False).astype(jnp.float32))

    args = [jax.ShapeDtypeStruct((1, T, h, d), jnp.bfloat16, sharding=one_chip)
            for h, d in ((20, 64), (10, 64), (10, 128))]
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


def test_femnist_cnn_round_program_compiles_for_one_v5e_chip(one_chip):
    """The production round program (``api.round_fn``) of the north star —
    FEMNIST CNN, 10 clients/round, batch 20 — lowered at its real round-0
    shapes for one described chip, and small against its 16 GB."""
    from fedml_tpu.algorithms import FedAvgAPI
    from fedml_tpu.config import DataConfig, FedConfig, RunConfig, TrainConfig
    from fedml_tpu.data.femnist_synth import femnist_synthetic
    from fedml_tpu.models import create_model

    cfg = RunConfig(
        data=DataConfig(batch_size=20),
        fed=FedConfig(
            client_num_in_total=10, client_num_per_round=10, comm_round=1,
            epochs=1,
        ),
        train=TrainConfig(client_optimizer="sgd", lr=0.1),
        model="cnn",
        seed=0,
    )
    api = FedAvgAPI(
        cfg, femnist_synthetic(num_clients=10, seed=0),
        create_model("cnn", "femnist", (28, 28, 1), 62),
    )
    fn, args = api.round_program(0)
    compiled = fn.lower(*_on(one_chip, args)).compile()

    text = compiled.as_text()
    assert "convolution" in text  # the CNN's convs are in the chip program
    # the pools' gradient is ops/pooling's written-out rule (PR 33), not the
    # transpose of reduce_window
    assert "select-and-scatter" not in text and "reduce-window" in text
    mem = compiled.memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < need < 1 << 30, need  # ~160 MB; the chip holds 16 GB
    n_params = sum(
        int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(api.global_vars)
    )
    assert n_params == 1_690_046  # the D the robust-stats case above uses
