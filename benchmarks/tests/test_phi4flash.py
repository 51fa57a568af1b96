"""The yardstick's own checks for ``phi-4-mini-flash-reasoning.silo2t4k-sambay``:
the reference's leaves are the built model's at the cell's size; a sound
rehearsal run is ``correct`` and its ``flush`` spans carry the constants that
the three readers this cell brought turn into shares (each ``None`` where
the spans lack them); the readers' hand counts at the published shapes; a
timed path broken underneath the harness and the int8 control are not
``correct``."""

import math
import pathlib

import jax
import pytest

from benchmarks import run
from benchmarks.lib import compare, fedavg_ref, feed as feed_mod, system, window
from test_faults import answer_altered, half_batch_left_out, state_unchanged

ROOT = pathlib.Path(__file__).resolve().parents[2]
METRICS = ROOT / "benchmarks" / "metrics"
CELL = "phi-4-mini-flash-reasoning.silo2t4k-sambay"
READERS = ("ssm.selective_kernel_site_pct", "ssm.selective_hbm_pct", "attention.diff_core_peak_pct")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def measure(sabotage=None):
    return run.measure(
        ["--workload", CELL, "--seed", "2147483659", "--seconds", "1", "--rehearse"],
        sabotage=sabotage,
    )


def read(name, made):
    return run.load_module(METRICS / f"{name}.py").read(made)


def test_the_reference_s_leaves_are_the_built_model_s():
    """At the cell's size: 577 178 752 held parameters, leaf by leaf."""
    from fedml_tpu.models import create_model

    bench = run.load_json(ROOT / "BENCHMARK.json")
    _, cfg, _, _, ref = run.load_cell(bench, CELL, rehearse=False)
    m = cfg["model"]
    model = create_model(m["name"], m["dataset"], tuple(m["input_shape"]), int(m["num_classes"]),
                         **m["kwargs"])
    have = system.flat_params(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    want = ref.param_shapes(cfg)
    assert {k: tuple(v.shape) for k, v in have.items()} == {k: tuple(v) for k, v in want.items()}
    assert sum(math.prod(s) for s in want.values()) == 577_178_752


def test_a_sound_run_is_correct_and_its_spans_feed_the_readers():
    tracer = system.get_tracer()
    t0 = tracer.now_us()
    out = measure()
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["compiles_in_window"] == 0
    spans = system.program_spans(tracer, t0)
    flushes = [a for n, _, _, a in spans if n == "flush"]
    # rehearsal: 2 silos x 8 documents of 32 tokens a round; published
    # layers 3..7 of 8 at width 64: 4 query heads on 2 key/value heads of 16,
    # a window of 8, a scan over 128 channels with a state of 16
    tokens = sum(a["rows"] for a in flushes) * 2 * 8 * 32
    made = {"program_spans": spans, "units": tokens, "chips": 1, "trace": {"window_s": 2.0},
            "peaks": PEAKS}
    a = flushes[0]
    assert {k: v for k, v in a.items() if k.startswith(("diff_", "sscan_", "attn_"))} == {
        "attn_sites": 6, "attn_kernel_sites": 0, "sscan_sites": 1, "sscan_kernel_sites": 0,
        "sscan_layers": 1, "sscan_inner": 128, "sscan_state": 16, "diff_length": 32,
        "diff_window": 8, "diff_sliding_layers": 1, "diff_full_layers": 1,
        "diff_cross_layers": 1, "diff_pairs": 2, "diff_kv_groups": 1, "diff_key_dim": 16,
        "diff_value_dim": 32}
    assert read("ssm.selective_kernel_site_pct", made) == 0.0
    # 2 x (2 x 128 + 2 x 16) bytes in, 2 x 128 out, forward and backward
    per_token = (576 + 256) + (576 + 256 + 576)
    assert read("ssm.selective_hbm_pct", made) == pytest.approx(
        100 * tokens * per_token / 2.0 / 819e9)
    # window: 8 x 9 / 2 + 24 x 8 = 228 live pairs; full and cross: 32 x 33 / 2 = 528
    per_document = 3.5 * 2 * (228 + 2 * 528) * 2 * 2 * (16 + 32)
    assert read("attention.diff_core_peak_pct", made) == pytest.approx(
        100 * tokens / 32 * per_document / 2.0 / 197e12)
    for name in READERS[1:]:
        assert 0 < read(name, made) <= 100
        assert read(name, dict(made, trace=None)) is None
    # the accepted readers find what they read on this model's spans too, and
    # those of other block shapes find nothing
    assert read("attention.kernel_site_pct", made) == 0.0
    for name in ("attention.gqa_core_peak_pct", "attention.core_peak_pct", "ssm.scan_hbm_pct",
                 "ssm.kernel_site_pct", "rope.kernel_site_pct", "conv.gated_hbm_pct",
                 "moe.held_pairs_per_token"):
        assert read(name, made) is None, name


def test_the_readers_find_nothing_on_a_program_without_the_constants():
    """The parent's spans (no ``sscan_*`` or ``diff_*``: Laguna's flush) and
    a window without a flush."""
    made = {"units": 4096, "chips": 1, "trace": {"window_s": 2.0}, "peaks": PEAKS}
    laguna = {"rows": 2, "attn_sites": 5, "attn_kernel_sites": 5, "attn_length": 4096,
              "attn_window": 512, "attn_full_layers": 2, "attn_full_heads": 48,
              "attn_full_head_dim": 128}
    for name in READERS:
        assert read(name, dict(made, program_spans=[("flush", 0.0, 1.0, laguna)])) is None
        assert read(name, dict(made, program_spans=[])) is None


def test_the_required_work_is_the_published_shapes():
    """At the cell's shapes: T = 4 096; window 1 966 336 live pairs, full and
    cross 8 390 656 each; 20 pairs on 10 groups, keys of 64, values of 128;
    the scan over 5 120 channels with a state of 16."""
    core = run.load_module(METRICS / "attention.diff_core_peak_pct.py")
    scan = run.load_module(METRICS / "ssm.selective_hbm_pct.py")
    assert core.live_pairs(4096, 512) == 1_966_336 and core.live_pairs(4096) == 8_390_656
    window_flops = core.core_flops(4096, 512, 20, 64, 128)
    full_flops = core.core_flops(4096, 0, 20, 64, 128)
    assert full_flops == 3.5 * 2 * 8_390_656 * 2 * 20 * 192
    # the scan core: 2 x (2 x 5120 + 32) bytes in, 2 x 5120 out
    assert scan.scan_bytes(5120, 16) == 3 * 20_544 + 2 * 10_240
    spans = [("flush", 0.0, 1.0, {
        "sscan_sites": 1, "sscan_kernel_sites": 1, "sscan_layers": 1, "sscan_inner": 5120,
        "sscan_state": 16, "diff_length": 4096, "diff_window": 512, "diff_sliding_layers": 1,
        "diff_full_layers": 1, "diff_cross_layers": 1, "diff_pairs": 20, "diff_kv_groups": 10,
        "diff_key_dim": 64, "diff_value_dim": 128})]
    made = {"program_spans": spans, "units": 65536, "chips": 1, "trace": {"window_s": 1.0},
            "peaks": PEAKS}
    assert read("ssm.selective_kernel_site_pct", made) == 100.0
    assert read("attention.diff_core_peak_pct", made) == pytest.approx(
        100 * 16 * (window_flops + 2 * full_flops) / 197e12)
    assert read("ssm.selective_hbm_pct", made) == pytest.approx(
        100 * 65536 * scan.scan_bytes(5120, 16) / 819e9)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out, answer_altered])
def test_a_broken_timed_path_is_not_correct(fault):
    out = measure(sabotage=fault)
    assert out["correct"] is False, out["compared"]


def test_control_is_not_correct():
    """The reference with int8 matmul operands, put in the program's place,
    fails the cell's own limits; the reference against itself passes them.
    At the rehearsal's widths with documents of 512 tokens, as
    ``test_laguna.py``'s control."""
    bench = run.load_json(ROOT / "BENCHMARK.json")
    _, cfg, cell, limits, ref = run.load_cell(bench, CELL, rehearse=True)
    cfg["model"]["input_shape"] = [512]
    cfg["population"]["sample"]["length"] = 512
    followed = window.FOLLOWED
    feed = feed_mod.Feed(cfg, cell, 11)
    sound = fedavg_ref.follow(ref, cfg, cell, feed, 11, followed, client_block=1)
    ops = fedavg_ref.Ops(**cfg["precision"]["control_ops"])
    low = fedavg_ref.follow(ref, cfg, cell, feed, 11, followed, ops=ops, client_block=1)
    assert compare.decide(compare.numbers(sound, sound), limits, 0)[0] is True
    correct, compared = compare.decide(compare.numbers(low, sound), limits, 0)
    assert correct is False, compared
