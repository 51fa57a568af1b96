"""The yardstick's own checks for ``kanana-2-30b-a3b.silo2b1``: a sound
rehearsal run is ``correct``, a timed path broken underneath the harness and
the int8 control are not, and the two readers this cell brought
(``moe.bias_moved_pair_pct``, ``attention.core_peak_pct``) give numbers on a
run's ``flush`` spans and ``None`` where the spans lack what they read."""

import pathlib

import pytest

from benchmarks import run
from benchmarks.lib import compare, fedavg_ref, feed as feed_mod, system, window
from test_faults import answer_altered, half_batch_left_out, state_unchanged

ROOT = pathlib.Path(__file__).resolve().parents[2]
METRICS = ROOT / "benchmarks" / "metrics"
CELL = "kanana-2-30b-a3b.silo2b1"
READERS = ["moe.bias_moved_pair_pct", "attention.core_peak_pct"]


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
    # rehearsal: 2 silos x 8 documents of 32 tokens a round, one dense and one
    # expert layer, top-2 of 8 experts with 4 held, heads of 16 | 8 with values of 16
    tokens = sum(a["rows"] for a in flushes) * 2 * 8 * 32
    made = {"program_spans": spans, "units": tokens, "chips": 1,
            "trace": {"window_s": 2.0}, "peaks": {"bf16_flops_per_s": 197e12}}
    a = flushes[0]
    assert (a["layers"], a["expert_layers"], a["top_k"], a["shared_width"]) == (1, 1, 2, 32)
    assert (a["attn_sites"], a["attn_kernel_sites"]) == (2, 0)
    assert (a["attn_qk_width"], a["attn_v_width"], a["attn_heads"], a["attn_length"],
            a["attn_layers"]) == (24, 16, 4, 32, 2)
    moved = sum(a["moe_bias_moved"] for a in flushes)
    assert read("moe.bias_moved_pair_pct", made) == 100 * moved / (tokens * 2 * 1)
    assert 0 < read("moe.bias_moved_pair_pct", made) < 50
    # 3.5 x 2 x 32^2 / 2 x 4 heads x (24 + 16) FLOPs a layer and document
    assert read("attention.core_peak_pct", made) == pytest.approx(
        100 * (tokens / 32) * 2 * 3.5 * 2 * 512 * 4 * 40 / 2.0 / 197e12)
    assert read("attention.core_peak_pct", dict(made, trace=None)) is None
    # the accepted expert readers find their counters on this model's spans too
    assert 0.7 < read("moe.held_pairs_per_token", made) < 1.3
    assert read("moe.bounded_call_pct", made) == 100.0


@pytest.mark.parametrize("reader", READERS)
def test_readers_find_nothing_on_spans_without_what_they_read(reader):
    """A grouped-query decoder's flush span (Mellum's: expert counters and
    the two site counts, no bias counter and no latent widths)."""
    spans = [("flush", 0.0, 10.0, {"first_round": 4, "last_round": 5, "rows": 2,
                                   "moe_pairs": 100.0, "moe_calls": 8.0, "layers": 4,
                                   "attn_sites": 4, "attn_kernel_sites": 4}),
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
