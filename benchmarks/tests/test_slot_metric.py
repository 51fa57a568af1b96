"""``moe.slot_kernel_site_pct``: the share worked out by hand on hand-made
``flush`` spans, 100 and 0 at its two ends, and ``None`` where no span
carries the pair (a model without routed experts, a program from before the
kernel); and on the spans of a rehearsal of the claimed cell, whose small
widths keep every slot sum on ``sum_readers``."""

import pathlib

from benchmarks import run
from benchmarks.lib import system

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
READER = "moe.slot_kernel_site_pct"
CELL = "mellum2-12b-a2.5b.silo2"


def read(name, made):
    return run.load_module(METRICS / f"{name}.py").read(made)


def flush(**attrs):
    return ("flush", 0.0, 10.0, dict(first_round=4, last_round=5, rows=2, **attrs))


def test_the_share_is_of_the_sites_summed_over_the_flushes():
    every = [flush(moe_slot_sites=8, moe_slot_kernel_sites=8, moe_pairs=100.0),
             ("round", 0.0, 5.0, {"round": 4}),
             flush(moe_slot_sites=8, moe_slot_kernel_sites=8, moe_pairs=90.0)]
    assert read(READER, {"program_spans": every}) == 100.0
    none = [flush(moe_slot_sites=8, moe_slot_kernel_sites=0)]
    assert read(READER, {"program_spans": none}) == 0.0
    assert read(READER, {"program_spans": every[:2] + none}) == 50.0


def test_spans_without_the_pair_read_nothing():
    """A model without routed experts (GPT-2's flush span), the parent's
    program (the grouped products' pair but not this one), and a window
    without a flush."""
    assert read(READER, {"program_spans": [flush(attn_sites=12, attn_kernel_sites=12)]}) is None
    parent = [flush(moe_grouped_sites=36, moe_kernel_sites=36, moe_pairs=100.0, top_k=8)]
    assert read(READER, {"program_spans": parent}) is None
    assert read(READER, {"program_spans": []}) is None
    # the accepted reader beside it still reads its own pair from such spans
    assert read("moe.kernel_site_pct", {"program_spans": parent}) == 100.0


def test_a_rehearsal_of_silo2_carries_the_pair_and_keeps_the_xla_form():
    tracer = system.get_tracer()
    t0 = tracer.now_us()
    out = run.measure(["--workload", CELL, "--seed", "2147485071", "--seconds", "1", "--rehearse"])
    assert out["correct"] is True, out["compared"]
    spans = system.program_spans(tracer, t0)
    flushes = [a for n, _, _, a in spans if n == "flush"]
    # two expert layers, two sums each; rows under a lane tile
    assert flushes and all((a["moe_slot_sites"], a["moe_slot_kernel_sites"]) == (4, 0)
                           for a in flushes)
    assert read(READER, {"program_spans": spans}) == 0.0
