"""The yardstick's own checks for ``lfm2-8b-a1b.silo2t4k``: a sound
rehearsal run is ``correct`` and its ``flush`` spans feed the reader this
cell brought (``conv.gated_hbm_pct``), which gives ``None`` where the spans
lack the conv layers' constants; a timed path broken underneath the harness
and the int8 control are not ``correct``."""

import pathlib

import pytest

from benchmarks import run
from benchmarks.lib import compare, fedavg_ref, feed as feed_mod, system, window
from test_faults import answer_altered, half_batch_left_out, state_unchanged

ROOT = pathlib.Path(__file__).resolve().parents[2]
METRICS = ROOT / "benchmarks" / "metrics"
CELL = "lfm2-8b-a1b.silo2t4k"
READER = "conv.gated_hbm_pct"


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
    # rehearsal: 2 silos x 8 documents of 32 tokens a round; conv + dense,
    # attention + experts, conv + experts at width 64; top-2 of 8 experts, 4 held
    tokens = sum(a["rows"] for a in flushes) * 2 * 8 * 32
    made = {"program_spans": spans, "units": tokens, "chips": 1, "trace": {"window_s": 2.0},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    a = flushes[0]
    assert (a["conv_layers"], a["conv_width"]) == (2, 64)
    assert (a["attn_sites"], a["attn_kernel_sites"]) == (1, 0)
    assert (a["layers"], a["expert_layers"], a["top_k"]) == (2, 2, 2)
    # (4 + 7) x 64 numbers of 2 bytes a token and conv layer
    assert read(READER, made) == pytest.approx(100 * tokens * 2 * 11 * 64 * 2 / 2.0 / 819e9)
    assert 0 < read(READER, made) < 100
    assert read(READER, dict(made, trace=None)) is None
    # the accepted expert readers find their counters on this model's spans too
    assert 0.7 < read("moe.held_pairs_per_token", made) < 1.3
    assert read("moe.bounded_call_pct", made) == 100.0
    assert 0 < read("moe.bias_moved_pair_pct", made) < 50
    assert read("attention.kernel_site_pct", made) == 0.0
    # not this model's: its one attention site is no latent site
    assert read("attention.core_peak_pct", made) is None


def test_the_reader_finds_nothing_on_spans_without_the_conv_constants():
    """A decoder of attention layers only (Kanana's flush span: expert
    counters, site counts and latent widths, no conv constants), and a
    window without a flush."""
    spans = [("flush", 0.0, 10.0, {"first_round": 4, "last_round": 5, "rows": 2,
                                   "moe_pairs": 100.0, "moe_calls": 8.0, "layers": 4,
                                   "attn_sites": 5, "attn_kernel_sites": 5, "attn_qk_width": 192}),
             ("round", 0.0, 5.0, {"round": 4})]
    made = {"program_spans": spans, "units": 1024, "chips": 1, "trace": {"window_s": 2.0},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert read(READER, made) is None
    assert read(READER, dict(made, program_spans=[])) is None


def test_the_required_bytes_are_eleven_numbers_a_channel():
    bytes_of = run.load_module(METRICS / f"{READER}.py").conv_bytes
    assert bytes_of(2048) == 11 * 2048 * 2 and bytes_of(2048, 4) == 11 * 2048 * 4


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out, answer_altered])
def test_a_broken_timed_path_is_not_correct(fault):
    out = measure(sabotage=fault)
    assert out["correct"] is False, out["compared"]


def test_control_is_not_correct():
    """The reference with int8 matmul operands (the grouped products' too),
    put in the program's place at the rehearsal size, fails the cell's own
    limits; the reference against itself passes them."""
    bench = run.load_json(ROOT / "BENCHMARK.json")
    _, cfg, cell, limits, ref = run.load_cell(bench, CELL, rehearse=True)
    followed = window.FOLLOWED
    feed = feed_mod.Feed(cfg, cell, 11)
    sound = fedavg_ref.follow(ref, cfg, cell, feed, 11, followed, client_block=1)
    ops = fedavg_ref.Ops(**cfg["precision"]["control_ops"])
    low = fedavg_ref.follow(ref, cfg, cell, feed, 11, followed, ops=ops, client_block=1)
    assert compare.decide(compare.numbers(sound, sound), limits, 0)[0] is True
    correct, compared = compare.decide(compare.numbers(low, sound), limits, 0)
    assert correct is False, compared
