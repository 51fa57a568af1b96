"""The yardstick's own checks for ``nemotron-twotower-30b-a3b.silo2t4k-ssm``:
a sound rehearsal run is ``correct`` and its ``flush`` spans feed the two
readers this cell brought (``ssm.scan_hbm_pct``, ``moe.ungated_peak_pct``),
each of which gives ``None`` where the spans lack its attributes; a timed
path broken underneath the harness and the int8 control are not ``correct``."""

import pathlib

import pytest

from benchmarks import run
from benchmarks.lib import compare, fedavg_ref, feed as feed_mod, system, window
from test_faults import answer_altered, half_batch_left_out, state_unchanged

ROOT = pathlib.Path(__file__).resolve().parents[2]
METRICS = ROOT / "benchmarks" / "metrics"
CELL = "nemotron-twotower-30b-a3b.silo2t4k-ssm"
SCAN, UNGATED = "ssm.scan_hbm_pct", "moe.ungated_peak_pct"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def measure(sabotage=None):
    return run.measure(
        ["--workload", CELL, "--seed", "2147483659", "--seconds", "1", "--rehearse"],
        sabotage=sabotage,
    )


def read(name, made):
    return run.load_module(METRICS / f"{name}.py").read(made)


def test_a_sound_run_is_correct_and_its_spans_feed_both_readers():
    tracer = system.get_tracer()
    t0 = tracer.now_us()
    out = measure()
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["compiles_in_window"] == 0
    spans = system.program_spans(tracer, t0)
    flushes = [a for n, _, _, a in spans if n == "flush"]
    assert flushes and all(a["moe_dropped"] == 0 for a in flushes)
    # rehearsal: 2 silos x 8 documents of 32 tokens a round; M E M * E at
    # width 64: 8 state-space heads of 16 in 2 groups with a state of 16;
    # top-2 of 8 ungated experts of width 32, 4 held
    tokens = sum(a["rows"] for a in flushes) * 2 * 8 * 32
    made = {"program_spans": spans, "units": tokens, "chips": 1, "trace": {"window_s": 2.0},
            "peaks": PEAKS}
    a = flushes[0]
    assert [a[k] for k in ("ssm_layers", "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_groups",
                           "ssm_chunk")] == [2, 8, 16, 16, 2, 16]
    assert (a["attn_sites"], a["attn_kernel_sites"]) == (1, 0) and "rope_sites" not in a
    assert (a["layers"], a["expert_layers"], a["top_k"], a["expert_products"]) == (2, 2, 2, 2)
    # forward x, B, C (128 + 64 numbers of 2 B), the step (8 of 4 B) and y
    # (128); backward those, dy and the four gradients
    per_token = (384 + 32 + 256) + (384 + 32 + 256) + (384 + 32)
    assert read(SCAN, made) == pytest.approx(100 * tokens * 2 * per_token / 2.0 / 819e9)
    assert 0 < read(SCAN, made) < 100
    pairs = sum(a["moe_pairs"] for a in flushes)
    assert read(UNGATED, made) == pytest.approx(100 * pairs * 2 * 2 * 64 * 32 * 3 / 2.0 / 197e12)
    assert 0 < read(UNGATED, made) < 100
    for name in (SCAN, UNGATED):
        assert read(name, dict(made, trace=None)) is None
    # the accepted expert readers find their counters on this model's spans too
    assert 0.7 < read("moe.held_pairs_per_token", made) < 1.3
    assert read("moe.bounded_call_pct", made) == 100.0
    assert 0 < read("moe.bias_moved_pair_pct", made) < 50
    assert read("attention.kernel_site_pct", made) == 0.0
    # not this model's: no rotary call, no conv layer, no latent site
    for name in ("rope.kernel_site_pct", "conv.gated_hbm_pct", "attention.core_peak_pct"):
        assert read(name, made) is None


def test_both_readers_find_nothing_on_spans_without_their_attributes():
    """A decoder of gated experts and attention only (Kanana's flush span
    with the constant every expert model now carries, three products a
    pair), a span from before the constant existed, and a window without a
    flush."""
    attrs = {"first_round": 4, "last_round": 5, "rows": 2, "moe_pairs": 100.0, "moe_calls": 8.0,
             "layers": 4, "hidden": 2048, "expert_width": 768, "attn_sites": 5,
             "attn_kernel_sites": 5}
    made = {"units": 1024, "chips": 1, "trace": {"window_s": 2.0}, "peaks": PEAKS}
    for flush in (dict(attrs, expert_products=3), attrs):
        spans = [("flush", 0.0, 10.0, flush), ("round", 0.0, 5.0, {"round": 4})]
        assert read(SCAN, dict(made, program_spans=spans)) is None
        assert read(UNGATED, dict(made, program_spans=spans)) is None
        # the gated reader reads them, at three products a pair
        assert read("moe.expert_peak_pct", dict(made, program_spans=spans)) == pytest.approx(
            100 * 100 * 3 * 2 * 2048 * 768 * 3 / 2.0 / 197e12)
    assert read(SCAN, dict(made, program_spans=[])) is None
    assert read(UNGATED, dict(made, program_spans=[])) is None
    # two products a pair: the ungated reader's, at two thirds of the gated count
    spans = [("flush", 0.0, 10.0, dict(attrs, expert_products=2))]
    assert read(UNGATED, dict(made, program_spans=spans)) == pytest.approx(
        100 * 100 * 2 * 2 * 2048 * 768 * 3 / 2.0 / 197e12)


def test_the_required_bytes_and_flops_are_the_published_shapes():
    """At the published shapes: 54 016 bytes a token and layer through the
    scan's core, and 2 x 2 x 2688 x 1856 x 3 FLOPs a held pair."""
    scan_bytes = run.load_module(METRICS / f"{SCAN}.py").scan_bytes
    forward = (4096 + 2 * 1024) * 2 + 64 * 4 + 4096 * 2
    backward = (4096 + 2 * 1024) * 2 + 64 * 4 + 4096 * 2 + (4096 + 2 * 1024) * 2 + 64 * 4
    assert (forward, backward) == (20736, 33280)
    assert scan_bytes(64, 64, 8, 128) == forward + backward == 54016
    assert scan_bytes(64, 64, 8, 128, 4) == 2 * 54016 - 3 * 256
    pair_flops = run.load_module(METRICS / f"{UNGATED}.py").pair_flops
    assert pair_flops(2688, 1856) == 12 * 2688 * 1856
    gated = run.load_module(METRICS / "moe.expert_peak_pct.py").pair_flops
    assert 3 * pair_flops(2688, 1856) == 2 * gated(2688, 1856)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out, answer_altered])
def test_a_broken_timed_path_is_not_correct(fault):
    out = measure(sabotage=fault)
    assert out["correct"] is False, out["compared"]


def test_control_is_not_correct():
    """The reference with int8 matmul operands (the grouped products' too),
    put in the program's place, fails the cell's own limits; the reference
    against itself passes them. At the rehearsal's widths but with documents
    of 512 tokens: what the control shows on the chip is attention's
    probabilities rounded to nought under a per-tensor int8 scale (one key in
    thousands holds 1/T of a row's weight, the scale's step is 1/127), on the
    ``v_proj`` and ``o_proj`` leaves, and that takes hundreds of keys; at the
    rehearsal's 32 tokens the control reads ``first_change`` 0.011-0.015,
    under the limit (the precedent is ``femnist-cnn.c10``'s control test,
    which runs at the cell's own cohort)."""
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
    nums = compare.numbers(low, sound)
    correct, compared = compare.decide(nums, limits, 0)
    assert correct is False, compared
    assert nums["first_change_leaf"].endswith(("v_proj", "o_proj")), nums
