"""``ssm.kernel_site_pct``: the share worked out by hand on hand-made
``flush`` spans, 100 and 0 at its two ends, and ``None`` where no span
carries the pair (a model without state-space layers, a program from before
the kernels); and on the spans of a rehearsal of the cell it was added for,
whose small widths keep every scan on the chunked products."""

import pathlib

from benchmarks import run
from benchmarks.lib import system

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
READER = "ssm.kernel_site_pct"
CELL = "nemotron-twotower-30b-a3b.silo2t4k-ssm"


def read(name, made):
    return run.load_module(METRICS / f"{name}.py").read(made)


def flush(**attrs):
    return ("flush", 0.0, 10.0, dict(first_round=4, last_round=5, rows=2, **attrs))


def test_the_share_is_of_the_sites_summed_over_the_flushes():
    every = [flush(ssd_sites=3, ssd_kernel_sites=3, ssm_layers=3), ("round", 0.0, 5.0, {"round": 4}),
             flush(ssd_sites=3, ssd_kernel_sites=3, ssm_layers=3)]
    assert read(READER, {"program_spans": every}) == 100.0
    none = [flush(ssd_sites=3, ssd_kernel_sites=0, ssm_layers=3)]
    assert read(READER, {"program_spans": none}) == 0.0
    assert read(READER, {"program_spans": every[:2] + none}) == 100.0 * 3 / 6


def test_spans_without_the_pair_read_nothing():
    """A model without state-space layers (Mellum's flush span), the parent's
    program (the constants of the layers but no pair), and a window without a
    flush."""
    assert read(READER, {"program_spans": [flush(attn_sites=4, attn_kernel_sites=4)]}) is None
    parent = [flush(ssm_layers=3, ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=8,
                    ssm_chunk=128, moe_grouped_sites=12, moe_kernel_sites=12)]
    assert read(READER, {"program_spans": parent}) is None
    assert read(READER, {"program_spans": []}) is None
    # the accepted reader beside it still reads its own pair from such spans
    assert read("moe.kernel_site_pct", {"program_spans": parent}) == 100.0


def test_the_rehearsal_of_the_ssm_cell_counts_its_scans_on_the_chunked_products():
    """Two ``M`` layers a step (``M E M * E``) at chunks of 16 and widths under
    a lane tile: two scans a flushed round, none on the kernels."""
    tracer = system.get_tracer()
    t0 = tracer.now_us()
    out = run.measure(["--workload", CELL, "--seed", "2147483659", "--seconds", "1", "--rehearse"])
    assert out["correct"] is True, out["compared"]
    spans = system.program_spans(tracer, t0)
    flushes = [a for n, _, _, a in spans if n == "flush"]
    assert flushes and all((a["ssd_sites"], a["ssd_kernel_sites"]) == (2, 0) for a in flushes)
    assert read(READER, {"program_spans": spans}) == 0.0
