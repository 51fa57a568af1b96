"""``embed.kernel_site_pct``: the share worked out by hand on hand-made
``flush`` spans, 100 and 0 at its two ends, and ``None`` where no span
carries the pair (a model without a decoder's token table, a program from
before the kernel); and the pair each decoder cell's own model states at its
training step."""

import pathlib

import pytest

from benchmarks import run

ROOT = pathlib.Path(__file__).resolve().parents[2]
METRICS = ROOT / "benchmarks" / "metrics"
READER = "embed.kernel_site_pct"


def read(made):
    return run.load_module(METRICS / f"{READER}.py").read(made)


def flush(**attrs):
    return ("flush", 0.0, 10.0, dict(first_round=4, last_round=5, rows=2, **attrs))


def test_the_share_is_of_the_sites_summed_over_the_flushes():
    every = [flush(embed_sites=1, embed_kernel_sites=1), ("round", 0.0, 5.0, {"round": 4}),
             flush(embed_sites=1, embed_kernel_sites=1)]
    assert read({"program_spans": every}) == 100.0
    none = [flush(embed_sites=1, embed_kernel_sites=0)]
    assert read({"program_spans": none}) == 0.0
    assert read({"program_spans": every[:2] + none}) == 50.0


def test_spans_without_the_pair_read_nothing():
    """GPT-2's flush span, the parent's SambaY flush (its scan's pair but not
    this one), and a window without a flush."""
    assert read({"program_spans": [flush(attn_sites=12, attn_kernel_sites=12)]}) is None
    parent = [flush(sscan_sites=1, sscan_kernel_sites=1, attn_sites=6, attn_kernel_sites=6)]
    assert read({"program_spans": parent}) is None
    assert read({"program_spans": []}) is None


@pytest.mark.parametrize("cell", [
    w for w in run.load_json(ROOT / "BENCHMARK.json")["per_layer"]
    if w["name"] == READER][0]["workloads"])
def test_every_listed_cell_s_step_takes_the_kernel(cell):
    """The cells the metric lists are those whose model states the pair, and
    each one's training step sends its table's gradient to the kernel."""
    from fedml_tpu.models import create_model

    bench = run.load_json(ROOT / "BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    spec = run.load_json(ROOT / "benchmarks" / "configs" / f"{entry['config']}.json")["model"]
    traffic = run.load_json(ROOT / "benchmarks" / "traffic" / f"{entry['traffic']}.json")
    model = create_model(spec["name"], spec["dataset"], tuple(spec["input_shape"]),
                         int(spec["num_classes"]), **spec.get("kwargs", {}))
    attrs = model.flush_attrs(traffic["batch_size"])
    assert read({"program_spans": [flush(**attrs)]}) == 100.0
