"""The readers of the program's own spans, counts and program names, each on
a hand-made ``run``: the value worked out by hand, and ``None`` where the
span or program it reads is absent (a program from before the spans)."""

import pathlib

import numpy as np
import pytest

from benchmarks import run as run_mod
from benchmarks.lib import trace

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"


def read(name, run):
    return run_mod.load_module(METRICS / f"{name}.py").read(run)


def span(name, start, end, **attrs):
    return (name, float(start), float(end), attrs)


def hand_made_run():
    """Two rounds and one evaluation in a window of 10 000 us. Times in us on
    the tracer's clock; ``to_trace_ns`` puts them on the trace's (x 1000, no
    offset). The device is busy in [1 000 000, 2 500 000) and
    [3 000 000, 9 000 000) ns."""
    spans = [
        # round 4: builds its own batch (nothing was prepared)
        span("round", 0, 1000, round=4, depth=0),
        span("select", 10, 40, round=4, parent="round", depth=1, clients=2),
        span("broadcast", 50, 650, round=4, parent="round", depth=1, prepared=False),
        span("stack", 60, 360, round=4, parent="broadcast", depth=2, steps=2, bs=4),
        span("place", 400, 600, round=4, parent="broadcast", depth=2,
             slots=16, real_samples=10.0),
        span("local_train", 700, 950, round=4, parent="round", depth=1),
        # round 5 prepared a round early: 2 000 us, of which the device was
        # busy for 500 (trace ns [2 000 000, 2 500 000)) and 1 000
        # ([3 000 000, 4 000 000))
        span("prepare", 2000, 4000, round=5, depth=0),
        span("select", 2010, 2100, round=5, parent="prepare", depth=1, clients=2),
        span("stack", 2200, 2900, round=5, parent="prepare", depth=1, steps=2, bs=4),
        span("place", 3000, 3900, round=5, parent="prepare", depth=1,
             slots=16, real_samples=6.0),
        span("health", 4100, 4200, first_round=4, last_round=4, depth=0, clients=2),
        span("round", 4300, 4800, round=5, depth=0),
        span("broadcast", 4310, 4320, round=5, parent="round", depth=1, prepared=True),
        span("local_train", 4400, 4700, round=5, parent="round", depth=1),
        span("health", 4900, 5000, first_round=5, last_round=5, depth=0, clients=2),
        span("flush", 5000, 9000, first_round=4, last_round=5, depth=0, rows=2),
        span("flush_wait", 5100, 7100, parent="flush", depth=1, rows=2),
        span("eval", 7300, 8300, round=5, parent="flush", depth=1),
        # an evaluation outside any flush is nobody's child here
        span("eval", 9500, 9600, round=5, depth=0),
    ]
    merged = (np.asarray([1.0e6, 3.0e6]), np.asarray([2.5e6, 9.0e6]))
    return {
        "rounds": 2, "elapsed_s": 0.010, "program_spans": spans, "bench_spans": [],
        "trace": {
            "merged": merged, "busy_s": 7.5e-3, "window_s": 10e-3,
            "programs": {
                "jit_round_fn(7)": 5.0e-3, "jit_device_store_gather(3)": 0.8e-3,
                "jit_device_store_gather(4)": 0.4e-3, "jit_eval_fn(9)": 0.3e-3,
            },
        },
        "to_trace_ns": lambda us: us * 1e3, "covered": trace.covered,
    }


def without(run, *names):
    out = dict(run)
    out["program_spans"] = [s for s in run["program_spans"] if s[0] not in names]
    return out


def parent_like(run):
    """The spans of a program from before this change: ``round``,
    ``broadcast`` (no ``prepared``), ``local_train``, ``eval``; the gather
    program still called ``jit_fn``."""
    out = dict(run)
    out["program_spans"] = [
        (n, s, e, {k: v for k, v in a.items() if k != "prepared"})
        for n, s, e, a in run["program_spans"]
        if n in ("round", "broadcast", "local_train", "eval")
    ]
    programs = {k.replace("device_store_gather", "fn"): v
                for k, v in run["trace"]["programs"].items()}
    out["trace"] = dict(run["trace"], programs=programs)
    return out


# (reader, hand-computed value, spans whose absence makes it None)
CASES = [
    # flush 4000 less flush_wait 2000 and the eval beneath it 1000, over 2 rounds
    ("loop.log_ms", (4000 - 2000 - 1000) / 1e3 / 2, ("flush",)),
    # window 10 000 less depth-0 spans: rounds 1000 + 500, prepare 2000,
    # health 100 + 100, flush 4000, the stray eval 100
    ("loop.unspanned_ms", (10000 - 7800) / 1e3 / 2, ("flush",)),
    # prepare 2000 + broadcasts 600 + 10
    ("host.prepare_ms", 2610 / 1e3 / 2, ("broadcast",)),
    ("host.stack_ms", (300 + 700) / 1e3 / 2, ("stack",)),
    ("pipeline.prepare_hidden_pct", 100.0 * 1500 / 2000, ("prepare",)),
    ("pipeline.stash_hit_pct", 50.0, ("broadcast",)),
    ("batch.placed_useful_pct", 100.0 * 16 / 32, ("place",)),
    ("store.gather_device_ms", 1e3 * 1.2e-3 / 2, ()),
    # 0.3 ms of eval_fn over the two eval spans
    ("eval.device_ms", 1e3 * 0.3e-3 / 2, ("eval",)),
]


@pytest.mark.parametrize("name,value,_", CASES, ids=[c[0] for c in CASES])
def test_reader_gives_the_hand_computed_value(name, value, _):
    assert read(name, hand_made_run()) == pytest.approx(value, rel=1e-12)


@pytest.mark.parametrize("name,_,absent", [c for c in CASES if c[2]],
                         ids=[c[0] for c in CASES if c[2]])
def test_reader_gives_none_without_its_span(name, _, absent):
    assert read(name, without(hand_made_run(), *absent)) is None


NEEDS_NEW_PROGRAM = [c[0] for c in CASES if c[0] != "eval.device_ms"]


@pytest.mark.parametrize("name", NEEDS_NEW_PROGRAM)
def test_reader_gives_none_on_a_program_without_the_spans(name):
    """The driver lays these readers over the parent's checkout too: there
    they find nothing to read, and say so without raising."""
    assert read(name, parent_like(hand_made_run())) is None


def test_eval_device_ms_reads_the_parent_too():
    # ``eval`` spans and ``eval_fn`` are older than this change
    assert read("eval.device_ms", parent_like(hand_made_run())) == pytest.approx(0.15)


@pytest.mark.parametrize("name", ["pipeline.prepare_hidden_pct", "store.gather_device_ms",
                                  "eval.device_ms"])
def test_trace_readers_give_none_in_an_untraced_run(name):
    assert read(name, dict(hand_made_run(), trace=None)) is None


def test_every_new_reader_is_declared_and_found():
    bench = run_mod.load_json(run_mod.ROOT / "BENCHMARK.json")
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name, *_ in CASES:
        assert name in declared and declared[name]["moves"] == "rounds_per_s"
        assert (METRICS / f"{name}.py").exists()


# -- tools/anatomy.py: the reductions that need no chip --------------------

def test_anatomy_scope_and_layer_of_an_op_name():
    from benchmarks.tools import anatomy

    bwd = ("jit(round_fn)/local_train/vmap()/while/body/closed_call/forward_backward/"
           "transpose(jvp(TransformerLM))/block3/attention/dot_general")
    assert anatomy.scope_of(bwd) == "local_train/forward_backward"
    assert anatomy.layer_of(bwd) == "bwd:block*/attention"
    assert anatomy.layer_of(bwd.replace("transpose(jvp(TransformerLM))", "jvp(TransformerLM)")
                            .replace("block3/attention", "head")) == "fwd:head"
    # the primitive at the end is no scope: jnp.take lowers to ``gather``
    assert anatomy.scope_of("jit(round_fn)/local_train/while/body/gather") == "local_train"
    assert anatomy.scope_of("jit(device_store_gather)/gather/jit(_take)/gather") == "gather"
    assert anatomy.scope_of("") == "" and anatomy.layer_of("jit(f)/mul") == ""


def test_anatomy_reads_op_names_by_instruction_and_result_type():
    from benchmarks.tools import anatomy

    text = (
        '  %fusion.8 = f32[3136,512]{1,0:T(8,128)} fusion(f32[20,3136]{1,0} %p), kind=kOutput, '
        'metadata={op_name="jit(round_fn)/local_train/forward_backward/mul" source_line=3}\n'
        '  ROOT %copy.2 = (f32[8]{0}, u32[]) copy-start(%a), metadata={op_name="x/gather/take"}\n'
        '  %bare.1 = f32[2]{0} add(%a, %b)\n')
    found = {(i, anatomy.result_type(t)): n for i, t, n in anatomy._INSTRUCTION.findall(text)}
    assert found == {("fusion.8", "f32[3136,512]"): "jit(round_fn)/local_train/forward_backward/mul",
                     ("copy.2", "(f32[8]"): "x/gather/take"}


def test_anatomy_device_time_per_scope_and_program():
    from benchmarks.tools import anatomy

    loaded = {
        "modules": [("jit_round_fn(1)", 0, 1000), ("jit_eval_fn(2)", 1000, 500)],
        "ops": [("%fusion.1 = f32[8]{0} fusion(...)", 0, 600),
                ("%copy.3 = f32[8]{0} copy(...)", 600, 400),
                ("%while.2 = (s32[]) while(...)", 0, 1000),  # a container: not an op of its own
                ("%fusion.1 = f32[8]{0} fusion(...)", 1000, 500)],  # the same name, another program
    }
    hlo = {"jit_round_fn": {("fusion.1", "f32[8]"): "jit(round_fn)/local_train/forward_backward/"
                                                     "jvp(CNN)/linear_1/dot_general"},
           "jit_eval_fn": {("fusion.1", "f32[8]"): "jit(eval_fn)/eval/while/body/add"}}
    out = anatomy.device_anatomy(loaded, 0, 1500, hlo)
    rnd = out["programs"]["jit_round_fn"]
    assert rnd["seconds"] == pytest.approx(1000e-9) and rnd["scoped_pct"] == pytest.approx(60.0)
    assert rnd["scopes"] == {"local_train/forward_backward": pytest.approx(600e-9),
                             "(no scope)": pytest.approx(400e-9)}
    assert rnd["layers"] == {"fwd:linear_1": pytest.approx(600e-9)}
    assert out["programs"]["jit_eval_fn"]["scopes"] == {"eval": pytest.approx(500e-9)}
    assert [r["op"] for r in out["largest_ops"]] == ["fusion.1", "fusion.1", "copy.3"]


def test_anatomy_clock_skew_matches_annotations_to_spans_in_order():
    from benchmarks.tools import anatomy

    # the tracer's 100 us is the trace's 1 000 000 ns: offset 900 000 ns
    spans = [span("round", 100, 200, round=1), span("round", 300, 400, round=2),
             span("flush", 500, 600)]
    host = [("bench.window", 1_000_000, 2_000_000),
            ("fedml.round", 1_000_500, 1_100_000), ("fedml.round", 1_203_000, 1_300_000),
            ("fedml.flush", 1_400_250, 1_500_000), ("fedml.round", 900_000, 950_000)]
    out = anatomy.clock_skew(host, spans, 1_000_000, 900_000)
    assert out["n"] == 3 and out["by_name"]["round"]["n"] == 2
    assert out["by_name"]["round"]["median_us"] == pytest.approx(1.75)
    assert out["by_name"]["flush"]["median_us"] == pytest.approx(0.25)
    assert out["largest_us"] == pytest.approx(3.0)
    assert out["median_us"] == pytest.approx(0.5)


def test_anatomy_host_self_time_and_unspanned():
    from benchmarks.tools import anatomy

    out = anatomy.host_anatomy(hand_made_run()["program_spans"], 0.010)
    assert out["flush"]["self_s"] == pytest.approx(1000e-6)  # less flush_wait and its eval
    assert out["prepare"]["self_s"] == pytest.approx((2000 - 90 - 700 - 900) * 1e-6)
    assert out["broadcast"]["n"] == 2 and out["broadcast"]["self_s"] == pytest.approx(110e-6)
    assert out["(unspanned)"]["total_s"] == pytest.approx(2200e-6)


def test_anatomy_idle_gaps_by_offset_and_by_annotation():
    from benchmarks.tools import anatomy

    # window [0, 10 000) ns, busy [1000, 4000) and [6000, 10 000): gaps
    # [0, 1000) and [4000, 6000). The tracer's clock is 1 us = 1000 ns, and
    # the harness's offset puts every span 1500 ns late.
    loaded = {"ops": [("%a = f32[1]{0} add(...)", 1000, 3000), ("%a = f32[1]{0} add(...)", 6000, 4000)],
              "host": [("fedml.round", 0, 9000), ("fedml.pack", 3500, 4500),
                       ("fedml.prepare", 4600, 9000)]}
    spans = [span("round", 0, 9), span("pack", 3.5, 4.5), span("prepare", 4.6, 9)]
    out = anatomy.idle_anatomy(loaded, spans, 0, 10_000, 1500)
    assert out["gaps"] == 2 and out["idle_s"] == pytest.approx(3000e-9)
    assert out["by_annotation"] == {"no_span_open": pytest.approx(1000e-9),
                                    "pack": pytest.approx(2000e-9)}
    # 1500 ns late, the second gap begins before ``pack`` does
    assert out["by_offset"] == {"no_span_open": pytest.approx(3000e-9)}
    assert out["longest"][0] == [pytest.approx(0.004), pytest.approx(0.002), "no_span_open", "pack"]
