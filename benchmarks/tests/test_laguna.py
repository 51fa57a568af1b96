"""The yardstick's own checks for ``laguna-xs.2.silo2t4k-swa``: a sound
rehearsal run is ``correct`` and its ``flush`` spans carry the per-kind
attention constants that the reader this cell brought
(``attention.gqa_core_peak_pct``) turns into a share of the peak, which is
``None`` where the spans lack them; a timed path broken underneath the
harness and the int8 control are not ``correct``."""

import pathlib

import pytest

from benchmarks import run
from benchmarks.lib import compare, fedavg_ref, feed as feed_mod, system, window
from test_faults import answer_altered, half_batch_left_out, state_unchanged

ROOT = pathlib.Path(__file__).resolve().parents[2]
METRICS = ROOT / "benchmarks" / "metrics"
CELL = "laguna-xs.2.silo2t4k-swa"
CORE = "attention.gqa_core_peak_pct"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def measure(sabotage=None):
    return run.measure(
        ["--workload", CELL, "--seed", "2147483659", "--seconds", "1", "--rehearse"],
        sabotage=sabotage,
    )


def read(name, made):
    return run.load_module(METRICS / f"{name}.py").read(made)


def test_a_sound_run_is_correct_and_its_spans_feed_the_reader():
    tracer = system.get_tracer()
    t0 = tracer.now_us()
    out = measure()
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["compiles_in_window"] == 0
    spans = system.program_spans(tracer, t0)
    flushes = [a for n, _, _, a in spans if n == "flush"]
    assert flushes and all(a["moe_dropped"] == 0 for a in flushes)
    # rehearsal: 2 silos x 8 documents of 32 tokens a round; F S S S F at
    # width 64: 6 | 8 query heads of 16 on one K/V head, 8 of 16 dims turned
    # on the full layers, a window of 8; top-4 of 16 experts, 4 held
    tokens = sum(a["rows"] for a in flushes) * 2 * 8 * 32
    made = {"program_spans": spans, "units": tokens, "chips": 1, "trace": {"window_s": 2.0},
            "peaks": PEAKS}
    a = flushes[0]
    assert {k: v for k, v in a.items() if k.startswith("attn_") and k not in (
        "attn_sites", "attn_kernel_sites")} == {
        "attn_length": 32, "attn_window": 8,
        "attn_full_layers": 2, "attn_full_heads": 6, "attn_full_kv_heads": 1,
        "attn_full_head_dim": 16, "attn_full_rotary_dim": 8,
        "attn_sliding_layers": 3, "attn_sliding_heads": 8, "attn_sliding_kv_heads": 1,
        "attn_sliding_head_dim": 16, "attn_sliding_rotary_dim": 16}
    assert (a["attn_sites"], a["attn_kernel_sites"]) == (5, 0)
    assert (a["rope_sites"], a["rope_kernel_sites"]) == (10, 0)
    # full: 32 x 33 / 2 = 528 live pairs; sliding: 8 x 9 / 2 + 24 x 8 = 228
    per_document = 3.5 * 2 * (2 * 528 * 6 + 3 * 228 * 8) * 32
    want = 100 * tokens / 32 * per_document / 2.0 / 197e12
    assert read(CORE, made) == pytest.approx(want)
    assert 0 < read(CORE, made) <= 100
    assert read(CORE, dict(made, trace=None)) is None
    # the accepted readers find their numbers on this model's spans too
    assert 0.7 < read("moe.held_pairs_per_token", made) < 1.3
    assert read("moe.bounded_call_pct", made) == 100.0
    assert read("attention.kernel_site_pct", made) == 0.0
    assert read("rope.kernel_site_pct", made) == 0.0
    # not this model's: no selection bias, no latent site, no conv, no scan
    for name in ("moe.bias_moved_pair_pct", "attention.core_peak_pct", "conv.gated_hbm_pct",
                 "ssm.scan_hbm_pct"):
        assert read(name, made) is None


def test_the_reader_finds_nothing_on_spans_without_the_per_kind_constants():
    """Mellum's flush span (one head count, a window, no per-kind
    constants), a latent model's, and a window without a flush."""
    made = {"units": 4096, "chips": 1, "trace": {"window_s": 2.0}, "peaks": PEAKS}
    mellum = {"rows": 2, "attn_sites": 4, "attn_kernel_sites": 4, "rope_sites": 8,
              "rope_kernel_sites": 8}
    latent = dict(mellum, attn_length=2048, attn_layers=5, attn_heads=32, attn_qk_width=192,
                  attn_v_width=128)
    for flush in (mellum, latent):
        assert read(CORE, dict(made, program_spans=[("flush", 0.0, 1.0, flush)])) is None
    assert read(CORE, dict(made, program_spans=[])) is None


def test_the_required_flops_are_the_published_shapes():
    """At the cell's shapes: T = 4 096, a full layer's 8 390 656 live pairs
    and a sliding layer's 1 966 336 (131 328 in the first 512 rows, then 512
    a row), 48 | 64 query heads of 128, two full and three sliding layers."""
    module = run.load_module(METRICS / f"{CORE}.py")
    assert module.live_pairs(4096) == 4096 * 4097 // 2 == 8_390_656
    assert module.live_pairs(4096, 512) == 512 * 513 // 2 + 3584 * 512 == 1_966_336
    assert module.live_pairs(4096, 512) == sum(min(i + 1, 512) for i in range(4096))
    assert module.live_pairs(64, 512) == module.live_pairs(64)
    full = module.core_flops(4096, 0, 48, 128)
    sliding = module.core_flops(4096, 512, 64, 128)
    assert full == 3.5 * 2 * 8_390_656 * 48 * 256
    # a window layer's core is over four times cheaper a head than a full one's
    assert 4.2 < (full / 48) / (sliding / 64) < 4.3
    spans = [("flush", 0.0, 1.0, {
        "attn_length": 4096, "attn_window": 512, "attn_full_layers": 2, "attn_full_heads": 48,
        "attn_full_head_dim": 128, "attn_sliding_layers": 3, "attn_sliding_heads": 64,
        "attn_sliding_head_dim": 128})]
    made = {"program_spans": spans, "units": 65536, "chips": 1, "trace": {"window_s": 1.0},
            "peaks": PEAKS}
    assert read(CORE, made) == pytest.approx(100 * 16 * (2 * full + 3 * sliding) / 197e12)


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out, answer_altered])
def test_a_broken_timed_path_is_not_correct(fault):
    out = measure(sabotage=fault)
    assert out["correct"] is False, out["compared"]


def test_control_is_not_correct():
    """The reference with int8 matmul operands (the grouped products' too),
    put in the program's place, fails the cell's own limits; the reference
    against itself passes them. At the rehearsal's widths but with documents
    of 512 tokens, as ``test_nemotron.py``'s control: at 32 tokens int8
    moves nothing enough (``first_change`` 0.007)."""
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
    # attention's probabilities rounded to nought under a per-tensor scale
    # (0.12 on layers_0/v_proj when written)
    assert nums["first_change_leaf"].endswith(("v_proj", "o_proj")), nums
