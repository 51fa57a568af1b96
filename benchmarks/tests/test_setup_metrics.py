"""The readers beneath ``setup_s`` and ``compile.in_window`` (ISSUE 36), each
on a hand-made set-up: the value worked out by hand, ``None`` where the
program leaves no such span (the parent's program, which the driver lays
these files over too), events from the window on left out, and the two ways
the set-up's spans arrive (the program's tracer, ``run["setup_spans"]``)."""

import pathlib

import pytest

from benchmarks import run as run_mod
from benchmarks.lib import setup_spans as lib
from benchmarks.lib import system

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
S = 1e6  # the tracer's clock counts microseconds

SETUP_READERS = [
    "setup.trace_lower_s", "setup.cache_load_s", "setup.compile_s",
    "setup.cold_programs", "setup.first_call_rest_s", "setup.store_upload_s",
]
READERS = SETUP_READERS + ["compile.window_programs"]


def read(name, run):
    return run_mod.load_module(METRICS / f"{name}.py").read(run)


def span(name, start_s, end_s, **attrs):
    return (name, start_s * S, end_s * S, attrs)


def jit(phase, start_s, end_s, program, parent=None, **attrs):
    if parent is not None:
        attrs.update(parent=parent, depth=2)
    return span(f"jit_{phase}", start_s, end_s, program=program, **attrs)


WINDOW_S = 40.0


def hand_made_setup():
    """One miss, two hits and one compile that was never written (``off``),
    a ``store_upload`` inside ``api_init``, round 0's first call, the
    benchmark's own ``_norms`` under no span; then a window from 40 s whose
    round 7 loads one program more. In the order a tracer records them: a
    span when it ends, so a parent after its children."""
    return [
        # api_init 0-5 s: model.init's convert loads in 0.1 s
        jit("trace", 0.10, 0.11, "convert", "api_init"),
        jit("lower", 0.11, 0.15, "jit(convert)", "api_init"),
        jit("backend", 0.15, 0.25, "jit(convert)", "api_init", cache="hit",
            retrieval_s=0.09, saved_s=0.4),
        # store_upload 1-4 s inside it: the store's concatenate loads in 0.5 s
        jit("trace", 1.1, 1.2, "concatenate", "store_upload"),
        jit("lower", 1.2, 1.3, "jit(concatenate)", "store_upload"),
        jit("backend", 1.3, 1.8, "jit(concatenate)", "store_upload", cache="hit",
            retrieval_s=0.45, saved_s=2.0),
        span("store_upload", 1.0, 4.0, parent="api_init", depth=1, rows=10),
        span("api_init", 0.0, 5.0, depth=0, params=70),
        # round 0: local_train 7-29 s traces for 2 s (an inner jit inside
        # the outer trace), lowers for 1 s, compiles for 10 s
        jit("trace", 7.5, 8.0, "matmul", "local_train", round=0),
        jit("trace", 7.1, 9.1, "round_fn", "local_train", round=0),
        jit("lower", 9.1, 10.1, "jit(round_fn)", "local_train", round=0),
        jit("backend", 10.1, 20.1, "jit(round_fn)", "local_train", round=0, cache="miss"),
        span("local_train", 7.0, 29.0, round=0, parent="round", depth=1),
        span("round", 6.0, 30.0, round=0, depth=0),
        # the benchmark's own program, under no span
        jit("trace", 31.0, 31.1, "_norms"),
        jit("lower", 31.1, 31.2, "jit(_norms)"),
        jit("backend", 31.2, 31.7, "jit(_norms)", cache="off"),
        # the window
        jit("trace", 41.0, 41.5, "round_fn", "local_train", round=7),
        jit("lower", 41.5, 42.0, "jit(round_fn)", "local_train", round=7),
        jit("backend", 42.0, 44.0, "jit(round_fn)", "local_train", round=7, cache="hit"),
        span("local_train", 40.5, 45.0, round=7, parent="round", depth=1),
        span("round", WINDOW_S, 46.0, round=7, depth=0),
    ]


def run_of(spans):
    """As ``run.py`` builds it: ``program_spans`` from the window on, and the
    whole list handed over as ``setup_spans``."""
    return {
        "program_spans": [sp for sp in spans if sp[1] >= WINDOW_S * S],
        "setup_spans": spans,
    }


def without(spans, *names):
    return [sp for sp in spans if sp[0] not in names]


VALUES = {
    # [0.10, 0.15] + [1.1, 1.3] + [7.1, 10.1] (the inner trace inside) + [31.0, 31.2]
    "setup.trace_lower_s": 0.05 + 0.2 + 3.0 + 0.2,
    "setup.cache_load_s": 0.1 + 0.5,
    "setup.compile_s": 10.0 + 0.5,
    "setup.cold_programs": 2.0,
    # api_init 5 s (store_upload inside it, once) + local_train 22 s, less
    # the jit_* inside them: 0.15 + 0.7 + 13.0; _norms is under no span
    "setup.first_call_rest_s": 27.0 - 13.85,
    "setup.store_upload_s": 3.0,
    "compile.window_programs": 1.0,
}


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_the_hand_computed_value(name):
    assert read(name, run_of(hand_made_setup())) == pytest.approx(VALUES[name], rel=1e-9)


@pytest.mark.parametrize("name", READERS)
def test_reader_takes_the_setup_from_the_programs_tracer(name, monkeypatch):
    """``run.py`` hands over no ``setup_spans`` yet: the readers ask the
    tracer it reads (``lib.system.get_tracer``), which is never reset."""

    class Event:
        def __init__(self, sp):
            self.name, self.ts_us, self.attrs = sp[0], sp[1], sp[3]
            self.dur_us = sp[2] - sp[1]

    class FakeTracer:
        def events(self):
            return [Event(sp) for sp in hand_made_setup()]

    monkeypatch.setattr(system, "get_tracer", FakeTracer)
    run = run_of(hand_made_setup())
    del run["setup_spans"]
    assert read(name, run) == pytest.approx(VALUES[name], rel=1e-9)


@pytest.mark.parametrize("name", [n for n in READERS if n != "setup.store_upload_s"])
def test_reader_gives_none_on_a_program_without_the_jit_spans(name):
    parent_like = without(hand_made_setup(), *lib.JIT)
    assert read(name, run_of(parent_like)) is None


def test_store_upload_reads_the_parent_too_and_none_without_a_store():
    # the span is older than the listener (PR 25)
    parent_like = without(hand_made_setup(), *lib.JIT)
    assert read("setup.store_upload_s", run_of(parent_like)) == pytest.approx(3.0)
    assert read("setup.store_upload_s", run_of(without(hand_made_setup(), "store_upload"))) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_where_the_window_has_no_span(name):
    run = run_of(hand_made_setup())
    run["program_spans"] = []
    assert read(name, run) is None


def test_events_from_the_window_on_are_no_part_of_the_setup():
    names = {(n, a.get("round")) for n, _, _, a in lib.setup_spans(run_of(hand_made_setup()))}
    assert ("round", 0) in names and ("round", 7) not in names
    assert ("jit_backend", 7) not in names


def test_a_warm_setup_compiles_nothing():
    warm = [
        (n, s, e, dict(a, cache="hit") if n == "jit_backend" else a)
        for n, s, e, a in hand_made_setup()
    ]
    assert read("setup.compile_s", run_of(warm)) == 0.0
    assert read("setup.cold_programs", run_of(warm)) == 0.0
    assert read("setup.cache_load_s", run_of(warm)) == pytest.approx(0.1 + 0.5 + 10.0 + 0.5)


def test_window_programs_counts_the_jit_backend_spans_of_the_window():
    spans = hand_made_setup()
    quiet = [sp for sp in spans if not (sp[0] == "jit_backend" and sp[1] >= WINDOW_S * S)]
    assert read("compile.window_programs", run_of(quiet)) == 0.0
    again = jit("backend", 44.1, 44.2, "jit(eval_fn)", "eval", round=7, cache="miss")
    assert read("compile.window_programs", run_of(spans + [again])) == 2.0


def test_payers_are_span_instances_each_once():
    spans = hand_made_setup()
    assert lib.payers(lib.setup_spans(run_of(spans))) == [
        (0.0, 5.0 * S), (1.0 * S, 4.0 * S), (7.0 * S, 29.0 * S)]
    # a second program under the same local_train is the same payer
    more = spans + [jit("backend", 21.0, 22.0, "jit(aux)", "local_train", round=0, cache="hit")]
    assert len(lib.payers(lib.setup_spans(run_of(more)))) == 3


def test_union_counts_overlap_once():
    assert lib.union_s([(0, 4 * S), (1 * S, 2 * S), (3 * S, 6 * S), (8 * S, 9 * S)]) == 7.0
    assert lib.union_s([]) == 0.0


def test_list_finds_every_new_reader_by_name(capsys):
    assert run_mod.measure(["--list"]) is None
    lines = {ln.split()[0]: ln for ln in capsys.readouterr().out.splitlines() if ln.strip()}
    for name in READERS:
        assert name in lines and "MISSING" not in lines[name], lines.get(name)
    for name in SETUP_READERS:
        assert "moves=setup_s" in lines[name]
    assert "moves=rounds_per_s" in lines["compile.window_programs"]
