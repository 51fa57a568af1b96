"""``rope.kernel_site_pct``: the share worked out by hand on hand-made
``flush`` spans, ``None`` where no span carries the pair (a model without
rotate-half rotary, a program from before the operator), and the rehearsal
of the cell it was added for: a sound run whose short sequences send every
call to the plain form."""

import pathlib

from benchmarks import run
from benchmarks.lib import system

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
READER = "rope.kernel_site_pct"


def read(name, made):
    return run.load_module(METRICS / f"{name}.py").read(made)


def flush(**attrs):
    return ("flush", 0.0, 10.0, dict(first_round=4, last_round=5, rows=2, **attrs))


def test_the_share_is_of_the_sites_summed_over_the_flushes():
    spans = [flush(rope_sites=8, rope_kernel_sites=8, attn_sites=4, attn_kernel_sites=4),
             ("round", 0.0, 5.0, {"round": 4}),
             flush(rope_sites=8, rope_kernel_sites=4)]
    assert read(READER, {"program_spans": spans}) == 75.0
    assert read(READER, {"program_spans": spans[:2]}) == 100.0


def test_spans_without_the_pair_read_nothing():
    """Kanana's flush span (the pairs form is no call of the operator), the
    parent's program, and a window without a flush."""
    spans = [flush(attn_sites=4, attn_kernel_sites=4, moe_pairs=100.0)]
    assert read(READER, {"program_spans": spans}) is None
    assert read(READER, {"program_spans": []}) is None
    # the accepted reader beside it still reads its own pair from such spans
    assert read("attention.kernel_site_pct", {"program_spans": spans}) == 100.0


def test_a_rehearsal_of_silo2_carries_the_pair_and_takes_the_plain_form():
    tracer = system.get_tracer()
    t0 = tracer.now_us()
    out = run.measure(["--workload", "mellum2-12b-a2.5b.silo2", "--seed", "2147485050",
                       "--seconds", "1", "--rehearse"])
    assert out["correct"] is True, out["compared"]
    spans = system.program_spans(tracer, t0)
    flushes = [a for n, _, _, a in spans if n == "flush"]
    # two attention layers, q and k each; 32 positions are no block of rows
    assert flushes and all((a["rope_sites"], a["rope_kernel_sites"]) == (4, 0) for a in flushes)
    assert read(READER, {"program_spans": spans}) == 0.0
