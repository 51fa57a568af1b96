"""``ModelDef.flush_attrs``: the constants every ``flush`` span carries, which
the benchmark's per-layer metrics read (the ``*_kernel_site_pct`` counts, the
attention, conv, state-space and expert metrics' widths). The model states
them; the federated algorithms only pass them on.

The values per cell are pinned as the shapes alone give them: each cell's
model from its configuration file, the samples of a local step from its
traffic file, no parameter built."""

import ast
import json
import pathlib

import pytest

from fedml_tpu.models import create_model

ROOT = pathlib.Path(__file__).resolve().parents[1]

MOE = {"moe_kernel_sites": 36, "moe_grouped_sites": 36, "moe_slot_kernel_sites": 8,
       "moe_slot_sites": 8, "layers": 4, "expert_layers": 4, "expert_products": 3}

CELLS = {
    "gpt2-124m.silo4": {"attn_kernel_sites": 12, "attn_sites": 12},
    "femnist-cnn.c200": {},
    "femnist-cnn.c10": {},
    "mellum2-12b-a2.5b.silo2": {
        "attn_kernel_sites": 4, "attn_sites": 4, "rope_kernel_sites": 8, "rope_sites": 8,
        **MOE, "hidden": 2304, "expert_width": 896, "top_k": 8, "embed_kernel_sites": 1,
        "embed_sites": 1},
    "kanana-2-30b-a3b.silo2b1": {
        "attn_kernel_sites": 5, "attn_sites": 5, "attn_qk_width": 192, "attn_v_width": 128,
        "attn_heads": 32, "attn_length": 2048, "attn_layers": 5,
        **MOE, "hidden": 2048, "expert_width": 768, "top_k": 6, "shared_width": 1536,
        "embed_kernel_sites": 1, "embed_sites": 1},
    "lfm2-8b-a1b.silo2t4k": {
        "attn_kernel_sites": 1, "attn_sites": 1, "rope_kernel_sites": 2, "rope_sites": 2,
        **MOE, "moe_slot_kernel_sites": 0, "hidden": 2048, "expert_width": 1792, "top_k": 4,
        "conv_layers": 4, "conv_width": 2048, "embed_kernel_sites": 1, "embed_sites": 1},
    "nemotron-twotower-30b-a3b.silo2t4k-ssm": {
        "attn_kernel_sites": 1, "attn_sites": 1, "moe_kernel_sites": 18,
        "moe_grouped_sites": 18, "moe_slot_kernel_sites": 6, "moe_slot_sites": 6,
        "ssd_kernel_sites": 3, "ssd_sites": 3, "hidden": 2688, "expert_width": 1856,
        "layers": 3, "expert_layers": 3, "top_k": 6, "expert_products": 2,
        "shared_width": 3712, "ssm_layers": 3, "ssm_heads": 64, "ssm_head_dim": 64,
        "ssm_state": 128, "ssm_groups": 8, "ssm_chunk": 128, "embed_kernel_sites": 1,
        "embed_sites": 1},
    "laguna-xs.2.silo2t4k-swa": {
        "attn_kernel_sites": 5, "attn_sites": 5, "rope_kernel_sites": 10, "rope_sites": 10,
        **MOE, "hidden": 2048, "expert_width": 512, "top_k": 8, "shared_width": 512,
        "attn_length": 4096, "attn_window": 512, "attn_full_layers": 2, "attn_full_heads": 48,
        "attn_full_kv_heads": 8, "attn_full_head_dim": 128, "attn_full_rotary_dim": 64,
        "attn_sliding_layers": 3, "attn_sliding_heads": 64, "attn_sliding_kv_heads": 8,
        "attn_sliding_head_dim": 128, "attn_sliding_rotary_dim": 128, "embed_kernel_sites": 1,
        "embed_sites": 1},
    "phi-4-mini-flash-reasoning.silo2t4k-sambay": {
        "attn_kernel_sites": 6, "attn_sites": 6, "sscan_kernel_sites": 1, "sscan_sites": 1,
        "sscan_layers": 1, "sscan_inner": 5120, "sscan_state": 16, "diff_length": 4096,
        "diff_window": 512, "diff_sliding_layers": 1, "diff_full_layers": 1,
        "diff_cross_layers": 1, "diff_pairs": 20, "diff_kv_groups": 10, "diff_key_dim": 64,
        "diff_value_dim": 128, "embed_kernel_sites": 1, "embed_sites": 1},
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_each_cells_flush_constants_are_the_ones_its_metrics_read(cell):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    spec = json.loads(
        (ROOT / "benchmarks" / "configs" / f"{entry['config']}.json").read_text())["model"]
    traffic = json.loads(
        (ROOT / "benchmarks" / "traffic" / f"{entry['traffic']}.json").read_text())
    model = create_model(spec["name"], spec["dataset"], tuple(spec["input_shape"]),
                         int(spec["num_classes"]), **spec.get("kwargs", {}))
    assert model.flush_attrs(traffic["batch_size"]) == CELLS[cell]


def test_every_cell_is_pinned():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert {w["name"] for w in bench["workloads"]} == set(CELLS)


@pytest.mark.parametrize("name,shape,classes", [("lr", (6,), 3), ("cnn", (28, 28, 1), 10)])
def test_a_model_without_kernel_sites_gives_no_constants(name, shape, classes):
    assert create_model(name, "synthetic", shape, classes).flush_attrs(16) == {}


def test_the_algorithms_import_no_kernel():
    """Which kernel a site takes is the model's business: no federated
    algorithm imports ``fedml_tpu.ops``."""
    found = []
    for path in sorted((ROOT / "fedml_tpu" / "algorithms").glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            # the package imports absolutely: ``from fedml_tpu import ops`` too
            if isinstance(node, ast.Import):
                modules = [alias.name for alias in node.names]
            elif isinstance(node, ast.ImportFrom):
                modules = [f"{node.module}.{alias.name}" for alias in node.names]
            else:
                continue
            found += [f"{path.name}: {m}" for m in modules
                      if m == "fedml_tpu.ops" or m.startswith("fedml_tpu.ops.")]
    assert found == []
