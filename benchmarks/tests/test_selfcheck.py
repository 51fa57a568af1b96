"""Self-check of the yardstick: the trace reduction on a synthetic trace,
the window arithmetic on a fake clock, the FLOP copy against hand counts,
and the worst-leaf comparison."""

import json
import math
import pathlib
import time

import numpy as np
import pytest

from benchmarks import run
from benchmarks.lib import compare, fedavg_ref, flops, peaks, trace, window

ROOT = pathlib.Path(__file__).resolve().parents[2]


def line(events):
    names, ids, start, dur, seen = [], [], [], [], {}
    for n, s, d in events:
        ids.append(seen.setdefault(n, len(seen)))
        if len(names) < len(seen):
            names.append(n)
        start.append(s)
        dur.append(d)
    return names, np.asarray(ids), np.asarray(start, float), np.asarray(dur, float)


def synthetic_trace():
    # window [1000, 11000) ns; ops busy [1000,4000) [4000,5000) [7000,10000);
    # an op before the window and one straddling its end
    ops = line([
        ("%fusion.1 = f32[8,8]{1,0} fusion(...)", 1000, 3000),
        ("%copy.2 = f32[8]{0} copy(...)", 4000, 1000),
        ("%fusion.1 = f32[8,8]{1,0} fusion(...)", 7000, 3000),
        ("%fusion.1 = f32[8,8]{1,0} fusion(...)", 0, 500),
        ("%copy.2 = f32[8]{0} copy(...)", 10500, 1000),
        ("%while.9 = (s32[]) while(...)", 1000, 4000),  # a container: busy, but no op of its own
    ])
    modules = line([
        ("jit_round_fn(1)", 1000, 4000), ("jit_eval_fn(2)", 7000, 3000),
        ("jit_round_fn(1)", 10500, 1000),
    ])
    host = [("bench.window", 1000, 11000), ("bench.flush", 5200, 6900)]
    return {"chips": [{"ops": ops, "modules": modules}], "host": host}


def test_trace_reduction_on_a_synthetic_trace():
    spans = [("eval", 4900, 6000), ("bench.flush", 5200, 6900), ("local_train", 9000, 9500)]
    out = trace.reduce(synthetic_trace(), spans)
    assert out["window_s"] == pytest.approx(10000e-9)
    # busy: 3000 + 1000 + 3000 + 500 (the straddler, clipped)
    assert out["busy_s"] == pytest.approx(7500e-9)
    assert out["programs"]["jit_round_fn(1)"] == pytest.approx(4500e-9)
    assert out["programs"]["jit_eval_fn(2)"] == pytest.approx(3000e-9)
    top = dict(out["device_ops"])
    assert top["fusion.1_f32_8_8"] == pytest.approx(6000e-9)
    assert top["copy.2_f32_8"] == pytest.approx(1500e-9)
    assert not any(k.startswith("while") for k in top)
    # gaps: [5000,7000) began under eval (flush had not started), [10000,10500) under nothing
    gaps = dict(out["idle_gaps"])
    assert gaps == {"eval": pytest.approx(2000e-9), "no_span_open": pytest.approx(500e-9)}
    assert trace.covered(out["merged"], 4500, 7500) == pytest.approx(1000.0)


def test_merge_handles_nested_and_touching_intervals():
    lo, hi = trace.merge(np.array([5.0, 0.0, 1.0, 10.0]), np.array([5.0, 4.0, 1.0, 1.0]))
    assert list(lo) == [0.0, 5.0] and list(hi) == [4.0, 11.0]


def test_window_arithmetic():
    # 40 s at 0.62 rounds/s with evaluation every 5: 24.8 -> 25 rounds (+ the closing one)
    assert window.window_rounds(40, 0.62, 5) == 25
    assert window.window_rounds(40, 2.08, 20) == 80     # 83.2 -> 4 periods, 38.5 s
    assert window.window_rounds(40, 32.9, 20) == 1320   # 1316 -> 66 periods
    assert window.window_rounds(0.5, 0.64, 5) == 5      # never under one period
    p = window.plan(40, 0.62, 5)
    assert p["followed"] == (0, window.FOLLOWED) == (0, 3)
    assert p["warm"] == (5, 11) and p["window"] == (15, 41)
    assert p["evals_in_window"] == 6
    assert p["window"][0] % 5 == 0 and (p["window"][1] - 1) % 5 == 0
    assert window.plan(40, 0.62, 5, trace_periods=1)["window"] == (15, 21)
    # every cell's window is within half an evaluation period of --seconds
    for rate, cadence in ((0.62, 5), (2.08, 20), (32.9, 20)):
        n = window.window_rounds(40, rate, cadence)
        assert abs(n / rate - 40) <= 0.5 * cadence / rate
    with pytest.raises(ValueError):
        window.window_rounds(0, 1, 5)


def test_rate_is_rounds_over_the_time_they_took():
    clock = iter([100.0, 146.875])  # a fake clock round the one call
    t0 = next(clock)
    elapsed = next(clock) - t0
    reader = run.load_module(ROOT / "benchmarks" / "metrics" / "rounds_per_s.py")
    assert reader.read({"rounds": 31, "elapsed_s": elapsed}) == pytest.approx(31 / 46.875)
    with pytest.raises(ZeroDivisionError):
        reader.read({"rounds": 31, "elapsed_s": 0.0})
    tokens = run.load_module(ROOT / "benchmarks" / "metrics" / "tokens_per_s.py")
    samples = run.load_module(ROOT / "benchmarks" / "metrics" / "samples_per_s.py")
    line = {"units": 4096, "unit_name": "tokens", "elapsed_s": 2.0}
    assert tokens.read(line) == 2048.0 and samples.read(line) is None


def test_stretches_show_where_a_window_went():
    # flushes end at 10, 20 and 31.5 s of a window that began at 0: the last stretch stalled
    assert window.periods(0.0, [10.0, 20.0, 31.5]) == [10.0, 10.0, 11.5]
    us = 1e6
    spans = [("bench.flush", 8 * us, 10 * us), ("bench.log", 9.5 * us, 9.6 * us),
             ("bench.log", 9.6 * us, 9.7 * us), ("bench.prepare", 1 * us, 2 * us),
             ("bench.flush", 19 * us, 21.5 * us), ("bench.log", 21 * us, 21.4 * us),
             ("bench.flush", 21.6 * us, 21.6 * us)]  # the last one logged nothing
    out = window.flush_anatomy(0.0, spans)
    assert out["stretches_s"] == pytest.approx([10.0, 11.5])
    assert out["flush_waits_s"] == pytest.approx([1.5, 2.0])
    prog = [("local_train", r * 0.4 * us + (0.3 * us if r > 5 else 0), 0, {"round": r}) for r in range(10)]
    slow = window.slowest_rounds(prog + [("eval", 0, 1, {})])
    assert slow["median_s"] == pytest.approx(0.4)
    assert slow["longest"][0] == [5, pytest.approx(0.7), pytest.approx(2.0)]
    with window.Sleeper(step=0.01, late=0.005) as sleeper:
        t_end = time.perf_counter() + 0.08
        while time.perf_counter() < t_end:  # python code hands the lock over: the sleeper still wakes
            pass
    got = sleeper.reading()
    assert got["overslept_s"] >= 0.0 and got["late_s"] >= got["late_n"] * 0.005
    assert got["late_s"] <= got["overslept_s"] * max(got["late_n"], 1) + 1e-9


def test_useful_samples_come_from_what_the_program_placed():
    reader = run.load_module(ROOT / "benchmarks" / "metrics" / "batch.useful_sample_pct.py")
    assert reader.read({"placed": (160000 + 128000, 96000.0), "unit_name": "samples"}) == pytest.approx(100 / 3)
    assert reader.read({"placed": None, "unit_name": "samples"}) is None
    assert reader.read({"placed": (10, 5.0), "unit_name": "tokens"}) is None


def test_memory_pools_are_reported_apart():
    stats = {"bytes_in_use": 7, "peak_bytes_in_use": 2 * 2**30, "bytes_reserved": 3 * 2**30,
             "peak_bytes_reserved": 3 * 2**30}
    line = {"memory_stats": stats, "programs": [("jit_fn", 3 * 2**30, 1, 1, 1),
                                                ("jit_round_fn", 2**30, 1, 1, 1),
                                                ("jit_round_fn", 2**29, 1, 1, 1)]}
    m = ROOT / "benchmarks" / "metrics"
    assert run.load_module(m / "device.hbm_peak_gib.py").read(line) == 2.0
    assert run.load_module(m / "device.hbm_scratch_gib.py").read(line) == 3.0
    assert run.load_module(m / "round.temp_gib.py").read(line) == 1.0
    assert run.load_module(m / "round.temp_gib.py").read({"programs": None}) is None
    assert run.load_module(m / "round.temp_gib.py").read({"programs": [("jit_fn", 5, 1, 1, 1)]}) is None
    assert run.load_module(m / "device.hbm_scratch_gib.py").read({"memory_stats": {}}) is None
    assert run.memory_peak(stats) == 3 * 2**30 + 7


def _grad_flops(cfg_file, rehearse):
    import jax

    bench = run.load_json(ROOT / "BENCHMARK.json")
    cfg = run.load_json(ROOT / cfg_file)
    if rehearse:
        cfg = run.overlay(cfg, cfg["rehearse"])
    ref = run.load_module((ROOT / cfg_file).with_name(cfg["reference"]))
    shapes = {k: jax.ShapeDtypeStruct(v, "float32") for k, v in ref.param_shapes(cfg).items()}

    def loss(p, x, y):
        m = jax.numpy.ones((x.shape[0],))
        return fedavg_ref.task_loss(
            cfg["task"], ref.logits_fn(p, x, fedavg_ref.REFERENCE, cfg), y, m)[0]

    del bench
    return flops.fn_flops(jax.grad(loss), shapes, *ref.unit_batch(cfg)), cfg


def test_flop_copy_matches_the_cnn_hand_count():
    got, _ = _grad_flops("benchmarks/configs/femnist-cnn.json", False)
    conv1 = 2 * 28 * 28 * 32 * 25 * 1
    conv2 = 2 * 14 * 14 * 64 * 25 * 32
    d1, d2 = 2 * 3136 * 512, 2 * 512 * 62
    # forward + both backward products, except that the first layer's input
    # needs no gradient
    assert got == 2 * conv1 + 3 * (conv2 + d1 + d2)
    assert got == pytest.approx(72.55e6, rel=1e-3)


def test_flop_copy_matches_the_gpt2_hand_count():
    got, cfg = _grad_flops("benchmarks/configs/gpt2-124m.json", False)
    V, T, L, d = 50257, 1024, 12, 768
    matmul_params = L * 12 * d * d + d * V
    attention = L * 4 * T * T * d  # QK^T and PV, full T x T as written
    per_doc = 3 * (2 * T * matmul_params + attention)
    assert got == per_doc
    per_token = got / T
    assert per_token == pytest.approx(6 * matmul_params + 12 * L * T * d)
    assert per_token == pytest.approx(854.5e6, rel=1e-3)


def test_worst_leaf_gap_measures_against_the_larger_of_leaf_and_median():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-6}
    prog = {"a": 1.1, "b": 2.0, "c": 2e-6}
    gap, leaf = compare.worst_leaf_gap(prog, ref)
    assert leaf == "a" and gap == pytest.approx(0.1)  # c is judged against the median leaf
    assert compare.moving_leaves({"a": 1.0, "b": 1.0, "c": 1e-4}) == ["a", "b"]
    assert compare.worst_leaf_gap({"a": math.nan, "b": 1, "c": 1}, ref)[0] == math.inf


def test_decide_needs_a_limit_for_every_number():
    nums = {"loss_r0": 0.1, "eval_loss": 0.0, "first_change": 0.0, "change": 0.0,
            "first_change_leaf": "a", "change_leaf": "a"}
    lim = {"loss": 0.5, "eval_loss": 0.5, "first_change": 0.5, "change": 0.5}
    assert compare.decide(nums, lim, 0)[0] is True
    assert compare.decide(nums, lim, 1)[0] is False
    assert compare.decide(dict(nums, loss_r0=0.6), lim, 0)[0] is False
    with pytest.raises(KeyError):
        compare.decide(nums, {"loss": 1}, 0)


def test_peaks_are_published_and_an_unknown_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9000")
    for path in (ROOT / "benchmarks" / "peaks").glob("*.json"):
        table = json.loads(path.read_text())
        assert table["source"] and not any("f32" in k or "float32" in k for k in table)


def test_config_files_say_what_they_cut():
    bench = run.load_json(ROOT / "BENCHMARK.json")
    for entry in bench["configs"]:
        cfg = run.load_json(ROOT / entry["file"])
        assert cfg["reduced"] == entry["reduced"] and cfg["source"] == entry["source"]
        assert set(cfg.get("reduced_why", {})) == set(cfg["reduced"])
    gpt2 = run.load_json(ROOT / "benchmarks/configs/gpt2-124m.json")
    assert gpt2["eval_length"] == gpt2["population"]["sample"]["test_length"]
    assert gpt2["n_positions"] == gpt2["population"]["sample"]["length"]
    assert (gpt2["n_layer"], gpt2["n_head"], gpt2["n_embd"]) == tuple(
        gpt2["model"]["kwargs"][k] for k in ("num_layers", "num_heads", "embed_dim"))
