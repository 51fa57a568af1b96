"""The yardstick's own checks for ``mellum2-12b-a2.5b.silo2`` (the older test
files name their cells): a sound rehearsal run is ``correct``, a timed path
broken underneath the harness and the int8 control are not, and the four
``moe.*`` readers give numbers on a run's ``flush`` spans and ``None`` where
the spans carry no expert counters."""

import pathlib

import pytest

from benchmarks import run
from benchmarks.lib import compare, fedavg_ref, feed as feed_mod, system, window
from test_faults import answer_altered, half_batch_left_out, state_unchanged

ROOT = pathlib.Path(__file__).resolve().parents[2]
METRICS = ROOT / "benchmarks" / "metrics"
CELL = "mellum2-12b-a2.5b.silo2"
READERS = ["moe.held_pairs_per_token", "moe.load_max_over_mean", "moe.padded_row_pct",
           "moe.expert_peak_pct"]


def measure(sabotage=None):
    return run.measure(
        ["--workload", CELL, "--seed", "2147483659", "--seconds", "1", "--rehearse"],
        sabotage=sabotage,
    )


def read(name, made):
    return run.load_module(METRICS / f"{name}.py").read(made)


def test_a_sound_run_is_correct_and_its_spans_feed_the_readers():
    tracer = system.get_tracer()
    t0 = tracer.now_us()
    out = measure()
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["compiles_in_window"] == 0
    spans = system.program_spans(tracer, t0)
    flushes = [a for n, _, _, a in spans if n == "flush"]
    assert flushes and all(a["moe_dropped"] == 0 for a in flushes)
    # rehearsal: 2 silos x 8 documents of 32 tokens a round, 2 layers, top-2 of
    # 8 experts with 4 held: one held pair a token and layer when routing is even
    tokens = sum(a["rows"] for a in flushes) * 2 * 8 * 32
    made = {"program_spans": spans, "units": tokens, "chips": 1,
            "trace": {"window_s": 2.0}, "peaks": {"bf16_flops_per_s": 197e12}}
    pairs = sum(a["moe_pairs"] for a in flushes)
    assert read("moe.held_pairs_per_token", made) == pairs / (tokens * 2)
    assert 0.7 < read("moe.held_pairs_per_token", made) < 1.3
    assert read("moe.padded_row_pct", made) == pytest.approx(100 * (1 - pairs / (tokens * 2 * 2)))
    assert 1.0 <= read("moe.load_max_over_mean", made) < 2.0
    # 18 x hidden 64 x width 32 FLOPs a pair over 2 s of a 197 TFLOP/s chip
    assert read("moe.expert_peak_pct", made) == pytest.approx(
        100 * pairs * 18 * 64 * 32 / 2.0 / 197e12)
    assert read("moe.expert_peak_pct", dict(made, trace=None)) is None


@pytest.mark.parametrize("reader", READERS)
def test_readers_find_nothing_on_spans_without_expert_counters(reader):
    spans = [("flush", 0.0, 10.0, {"first_round": 4, "last_round": 5, "rows": 2}),
             ("round", 0.0, 5.0, {"round": 4})]
    made = {"program_spans": spans, "units": 1024, "chips": 1,
            "trace": {"window_s": 2.0}, "peaks": {"bf16_flops_per_s": 197e12}}
    assert read(reader, made) is None
    assert read(reader, dict(made, program_spans=[])) is None


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
