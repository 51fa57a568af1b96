"""bench.py --compare: the mechanical bench-to-bench regression oracle
(pure record comparison — no backend, no timing)."""

import importlib.util
import json
import os
import subprocess
import sys

_BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "bench.py")


def _bench_mod():
    spec = importlib.util.spec_from_file_location("bench_under_test", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_compare_records_builds_delta_table_and_flags_regressions():
    bench = _bench_mod()
    record = {
        "value": 40.0,
        "north_star": {"rounds_per_sec": 40.0},
        "north_star_bf16": {"rounds_per_sec": 30.0},
        "scale_1m": {"rounds_per_sec": 350.0},
        "flash_attention_s4096": {"flash_over_xla_speedup": 3.0},  # no r/s
        "process_cold_start": {"skipped": "no backend"},
    }
    baseline = {
        "value": 42.0,
        "north_star": {"rounds_per_sec": 42.0},   # -4.8% — inside tol
        "north_star_bf16": {"rounds_per_sec": 45.0},  # -33% — regression
        "scale_1m": {"rounds_per_sec": 300.0},    # +16.7% — improvement
    }
    out = bench.compare_records(record, baseline, tol_pct=10.0)
    s = out["sections"]
    assert s["north_star"]["delta_pct"] == -4.8
    assert "regressed" not in s["north_star"]
    assert s["north_star_bf16"]["delta_pct"] == -33.3
    assert s["north_star_bf16"]["regressed"]
    assert s["scale_1m"]["delta_pct"] == 16.7
    assert s["headline"]["delta_pct"] == -4.8
    # sections without comparable r/s on both sides appear without deltas
    # (flash has no rounds_per_sec; cold_start skipped this run)
    assert "flash_attention_s4096" not in s
    assert out["regressions"] and "north_star_bf16" in out["regressions"][0]
    assert out["regress_tol_pct"] == 10.0
    assert out["missing_sections"] == []
    # a section the BASELINE measured but this run lost is listed loudly
    # (not a regression — partial passes are routine under the budget)
    out2 = bench.compare_records(
        {"scale_1m": {"skipped": "wall cap"}}, baseline, tol_pct=10.0
    )
    assert out2["missing_sections"] == [
        "north_star", "north_star_bf16", "scale_1m",
    ]
    assert out2["regressions"] == []


def test_compare_records_clean_when_within_tolerance():
    bench = _bench_mod()
    record = {"value": 41.0, "north_star": {"rounds_per_sec": 41.0}}
    baseline = {"value": 42.0, "north_star": {"rounds_per_sec": 42.0}}
    out = bench.compare_records(record, baseline, tol_pct=10.0)
    assert out["regressions"] == []


def test_compare_against_unreadable_baseline_is_loud_not_fatal(tmp_path):
    bench = _bench_mod()
    out = bench._compare_against(
        {"value": 1.0}, str(tmp_path / "missing.json"), 10.0
    )
    assert "error" in out and out["regressions"] == []


def test_unreadable_baseline_fails_the_gate_not_silently_green(tmp_path):
    """A typo'd/deleted --compare path must NOT read as "no regressions"
    — the record still emits (with the error recorded), but finalize
    exits 4 so CI notices the gate never actually compared anything."""
    import time as _time

    bench = _bench_mod()
    detail = tmp_path / "detail.json"
    em = bench._Emitter(
        _time.perf_counter(), str(detail),
        compare_path=str(tmp_path / "nope.json"), regress_tol_pct=10.0,
    )
    em.update({"north_star": {"rounds_per_sec": 40.0}})
    assert em.finalize(partial=False) == 4
    rec = json.loads(detail.read_text())
    assert "error" in rec["compare"]
    assert rec["compare"]["regressions"] == []


def test_emitter_finalize_wires_compare_block_and_exit_code(
    tmp_path, capsys
):
    """The full finalize path (what the real process exits with): a
    baseline claiming impossible throughput forces a regression -> the
    record carries the compare block, the compact stdout line carries
    the regression count, and finalize returns exit code 4. Driven
    through _Emitter in-process — a real measured section is
    machine-dependent (the tiny section wall-caps on slow CPU boxes)
    and this contract is pure bookkeeping."""
    import time as _time

    bench = _bench_mod()
    baseline = tmp_path / "BENCH_prev.json"
    baseline.write_text(json.dumps({
        "value": 1e9, "north_star": {"rounds_per_sec": 1e9},
    }))
    detail = tmp_path / "detail.json"
    em = bench._Emitter(
        _time.perf_counter(), str(detail),
        compare_path=str(baseline), regress_tol_pct=10.0,
    )
    em.update({"north_star": {"rounds_per_sec": 40.0}})
    code = em.finalize(partial=False)
    assert code == 4
    rec = json.loads(detail.read_text())
    assert rec["compare"]["baseline_file"] == "BENCH_prev.json"
    assert rec["compare"]["regressions"]
    assert rec["compare"]["sections"]["north_star"]["regressed"]
    last_line = json.loads(
        capsys.readouterr().out.strip().splitlines()[-1]
    )
    assert last_line["compare"]["regressions"] >= 1
    assert last_line["compare"]["baseline"] == "BENCH_prev.json"
    # no baseline -> no compare block, clean exit (same record otherwise)
    em2 = bench._Emitter(_time.perf_counter(), str(tmp_path / "d2.json"))
    em2.update({"north_star": {"rounds_per_sec": 40.0}})
    assert em2.finalize(partial=False) == 0
    assert "compare" not in json.loads((tmp_path / "d2.json").read_text())


def test_bench_cli_parses_compare_flags():
    """argparse wiring smoke: --help documents the new flags without
    touching a backend (jax imports only after the probe)."""
    p = subprocess.run(
        [sys.executable, _BENCH, "--help"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert p.returncode == 0, p.stderr[-500:]
    assert "--compare" in p.stdout and "--regress_tol" in p.stdout
