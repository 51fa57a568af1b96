"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmarks/run.py --list
    python3 benchmarks/run.py --workload <cell> --rehearse   # CPU, tiny, counts only

One run: build the cell's ``FedAvgAPI`` from its files and ``--seed``, follow
the first rounds through ``train()`` (they feed ``correct``), warm up every
shape the window will use through ``train()`` itself, then measure ONE call
of ``train()`` over the window's rounds, to the device-synchronised end of
its last round. After the window: read the peak memory, free the program,
follow the same first rounds with the plain reference, compare, and print
one JSON line. Cells, configurations, traffic, systems, limits and per-layer
metrics are files found by name (see README.md); nothing here names one.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
WORK = ROOT / ".benchwork"


def fail(msg: str, code: int = 2):
    print(f"benchmarks/run.py: {msg}", file=sys.stderr)
    raise SystemExit(code)


def load_json(path: pathlib.Path) -> dict:
    if not path.exists():
        fail(f"missing file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(path: pathlib.Path):
    if not path.exists():
        fail(f"missing file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def overlay(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = copy.deepcopy(v)
    return out


def load_cell(bench: dict, workload: str, rehearse: bool):
    """(cell entry, configuration, cell, limits, reference module) of a
    workload, each from the file its name leads to. ``cell`` is the traffic
    file's mix with the configuration's learning rate beside it."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        fail(f"unknown workload {workload!r}; --list shows {sorted(cells)}")
    entry = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    cfg_path = ROOT / cfg_entry["file"]
    model_cfg = load_json(cfg_path)
    traffic = load_json(HERE / "traffic" / f"{entry['traffic']}.json")
    if rehearse:
        model_cfg = overlay(model_cfg, model_cfg.get("rehearse", {}))
        traffic = overlay(traffic, traffic.get("rehearse", {}))
    if int(traffic["chips"]) != int(entry["chips"]):
        fail(f"{workload}: BENCHMARK.json and the traffic file disagree on chips")
    limits_path = HERE / "limits" / f"{workload}.json"
    limits = load_json(limits_path) if limits_path.exists() else None
    ref = load_module(cfg_path.with_name(model_cfg["reference"]))
    cell = dict(traffic, lr=model_cfg["train"]["lr"])
    return entry, model_cfg, cell, limits, ref


def metrics_for(bench: dict, workload: str, section: str):
    return [
        m for m in bench[section]
        if "workloads" not in m or workload in m["workloads"]
    ]


def list_all(bench: dict):
    print("configurations:")
    for c in bench["configs"]:
        ok = (ROOT / c["file"]).exists()
        print(f"  {c['name']:<14} {c['file']}{'' if ok else '  MISSING'}")
    print("cells:")
    for w in bench["workloads"]:
        t = HERE / "traffic" / f"{w['traffic']}.json"
        lim = HERE / "limits" / f"{w['name']}.json"
        flags = ("" if t.exists() else "  traffic file MISSING") + (
            "" if lim.exists() else "  limits file MISSING")
        print(f"  {w['name']:<20} config={w['config']} traffic={w['traffic']} "
              f"chips={w['chips']}{flags}")
    print("end-to-end metrics:")
    for m in bench["end_to_end"]:
        r = HERE / "metrics" / f"{m['name']}.py"
        print(f"  {m['name']:<16} {m['unit']:<10} bound={m['bound']} "
              f"cells={m.get('workloads', 'all')}{'' if r.exists() else '  reader MISSING'}")
    print("per-layer metrics:")
    for m in bench["per_layer"]:
        r = HERE / "metrics" / f"{m['name']}.py"
        print(f"  {m['name']:<26} {m['unit']:<6} layer={m['layer']!r} moves={m['moves']} "
              f"cells={m.get('workloads', 'all')}{'' if r.exists() else '  reader MISSING'}")
    print("systems:", ", ".join(sorted(p.stem for p in (HERE / "systems").glob("*.json"))))
    print("peaks:", ", ".join(sorted(p.stem for p in (HERE / "peaks").glob("*.json"))))
    print("traffic files:", ", ".join(sorted(p.stem for p in (HERE / "traffic").glob("*.json"))))


def memory_peak(stats: dict) -> int:
    """Peak bytes a chip held. The TPU runtime books two disjoint pools:
    live buffers (``bytes_in_use``, with a peak) and the space it reserves
    for the loaded programs' temporaries (``bytes_reserved``, which only
    grows; what is left for buffers is the limit less both). Their sum after
    the window is what the chip holds then; the buffers' own peak may have
    come earlier (an upload's relayout). The larger of the two never
    overstates. The traced run reports the two pools apart
    (``device.hbm_peak_gib``, ``device.hbm_scratch_gib``) beside the round
    program's own temporaries (``round.temp_gib``)."""
    held_now = stats.get("bytes_in_use", 0) + stats.get("bytes_reserved", 0)
    return int(max(stats.get("peak_bytes_in_use", 0), held_now))


def window_faults(rows: list, first: int, end: int) -> tuple:
    """(rounds the window attempted, rounds that failed): a round fails when
    ``train()`` logged no row for it or its training loss is not finite."""
    seen = {}
    for r in rows:
        if isinstance(r, dict) and "Train/Loss" in r and first <= r.get("round", -1) < end:
            seen[r["round"]] = r["Train/Loss"]
    failed = sum(
        1 for r in range(first, end)
        if r not in seen or not math.isfinite(float(seen[r]))
    )
    return end - first, failed


def program_reading(rows: list, followed: int, norms_first, norms_last) -> dict:
    loss, evals = {}, {}
    for r in rows:
        if not (isinstance(r, dict) and "Train/Loss" in r):
            continue
        k = r.get("round")
        if k is not None and k < followed and k not in loss:
            loss[k] = float(r["Train/Loss"])
            if "Test/Loss" in r:
                evals[k] = (float(r["Test/Loss"]), float(r["Test/Acc"]))
    return {
        "loss": [loss.get(i, math.nan) for i in range(followed)],
        "eval": evals, "norms_first": norms_first, "norms_last": norms_last,
    }


def measure(argv=None, sabotage=None):
    """One run; returns the result line as a dict (None after --list).
    ``sabotage(api)`` is the tests' hook: it breaks the timed path underneath
    the harness, which then has to report ``correct`` false."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend; prints counts, never a rate")
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    if args.list:
        list_all(bench)
        return None
    if not args.workload:
        fail("--workload is required (or --list)")
    seconds = float(args.seconds if args.seconds is not None else bench["run_seconds"])
    split = {"interpreter_s": time.perf_counter() - T_PROCESS}
    t = time.perf_counter()
    import jax

    split["jax_import_s"] = time.perf_counter() - t
    entry, model_cfg, cell, limits, ref = load_cell(bench, args.workload, args.rehearse)
    if limits is None and not args.rehearse:
        fail(f"no limits file benchmarks/limits/{args.workload}.json: the cell is not calibrated")

    sys.path.insert(0, str(ROOT))
    t = time.perf_counter()
    try:
        import fedml_tpu  # noqa: F401
    except ImportError as e:
        fail(f"the program is not in this checkout ({e})", 4)
    from benchmarks.lib import compare, feed as feed_mod, flops, peaks
    from benchmarks.lib import system, trace as trace_mod, window

    split["program_import_s"] = time.perf_counter() - t
    t = time.perf_counter()
    devices = jax.devices()
    split["device_init_s"] = time.perf_counter() - t
    dev = devices[0]
    chips = int(entry["chips"])
    if not args.rehearse and (dev.platform != "tpu" or len(devices) < chips):
        fail(f"needs {chips} TPU chip(s); jax found {len(devices)} x {dev.platform}", 3)
    device_peaks = None if args.rehearse else peaks.peaks_for(dev.device_kind)
    round_ref = system.load_round_reference(cell)

    cache_dir = system.install_compile_cache()
    counter = system.CompileCounter()
    followed = window.FOLLOWED
    rounds = window.plan(
        seconds, float(cell["nominal_rounds_per_s"]), int(cell["eval_every"]),
        followed, int(cell["trace_eval_periods"]) if args.trace else 0,
    )
    split["import_and_device_s"] = time.perf_counter() - T_PROCESS

    def reserved_gb():
        return system.device_memory(devices[:chips]).get("bytes_reserved", 0) / 1e9

    # -- set-up ---------------------------------------------------------
    t = time.perf_counter()
    feed = feed_mod.Feed(model_cfg, cell, args.seed)
    split["population_made_s"] = time.perf_counter() - t

    t = time.perf_counter()
    rows: list = []
    api = system.build(model_cfg, cell, feed, args.seed, ref, rows)
    if sabotage is not None:
        sabotage(api)
    placements = system.PlacementLog(api)
    jax.block_until_ready(api.global_vars)
    split["api_built_population_placed_s"] = time.perf_counter() - t
    split["reserved_gb_after_build"] = reserved_gb()

    t = time.perf_counter()
    mark = counter.mark()
    norms_first, norms_last, split["first_round_s"] = system.follow(
        api, ref.init_params(args.seed, model_cfg), followed)
    split["followed_rounds_s"] = time.perf_counter() - t
    split["reserved_gb_after_followed"] = reserved_gb()

    t = time.perf_counter()
    w0, w1 = rounds["window"]
    system.run_rounds(api, *rounds["warm"])
    seen = {feed.shape_class(r) for r in list(range(followed)) + list(range(*rounds["warm"]))}
    for r in range(w0, w1):
        if feed.shape_class(r) not in seen:
            seen.add(feed.shape_class(r))
            system.run_rounds(api, r, r + 1)
    split["warm_up_s"] = time.perf_counter() - t
    split["reserved_gb_after_warm_up"] = reserved_gb()
    split.update({"setup_" + k: v for k, v in counter.since(mark).items()})
    split["shape_classes"] = len(seen)

    # The benchmark's own spans are on in every run, traced or not, so that
    # both kinds of run drive the same host path.
    tracer = system.get_tracer()
    spans = system.SpanLog(tracer)
    spans.wrap(api, "_pipeline_prepare", "bench.prepare")
    spans.wrap(api, "_flush_pending", "bench.flush")
    spans.wrap(api, "_log_round", "bench.log")
    trace_dir = WORK / f"trace-{args.workload}"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_PROCESS
    split["setup_s"] = setup_s
    print("setup " + json.dumps(split), file=sys.stderr, flush=True)

    # -- the window: one call of train() ---------------------------------
    mark = counter.mark()
    placed_mark = len(placements.placed)
    cpu_before = time.process_time()
    tracer_t0 = tracer.now_us()
    with window.Sleeper() as sleeper, jax.profiler.TraceAnnotation(trace_mod.WINDOW_MARK):
        t0 = time.perf_counter()
        system.run_rounds(api, w0, w1)
        elapsed = time.perf_counter() - t0
    cpu_s = time.process_time() - cpu_before
    in_window = counter.since(mark)
    placed = placements.since(placed_mark)
    if args.trace:
        jax.profiler.stop_trace()
    gc.unfreeze()

    memory_stats = system.device_memory(devices[:chips])
    hbm_peak = memory_peak(memory_stats)
    n_rounds = w1 - w0
    attempted, failed = window_faults(rows, w0, w1)
    real = feed.real_samples(w0, w1)
    units = real * feed.units_per_sample
    unit_name = "tokens" if feed.kind == "tokens" else "samples"
    prog = program_reading(rows, followed, norms_first, norms_last)
    program_spans = system.program_spans(tracer, tracer_t0)
    schedule = system.schedule_of(api)
    anatomy = window.flush_anatomy(tracer_t0, spans.spans)

    # -- the traced window's numbers --------------------------------------
    traced = None
    offset_ns = 0.0
    programs = None
    if args.trace:
        loaded = trace_mod.load(trace_mod.find_xplane(str(trace_dir)))
        lo, _ = trace_mod.window_of(loaded["host"])
        offset_ns = lo - tracer_t0 * 1e3
        on_trace = [(n, s * 1e3 + offset_ns, e * 1e3 + offset_ns)
                    for n, s, e, _ in program_spans if n != "round"]
        on_trace += [(n, s * 1e3 + offset_ns, e * 1e3 + offset_ns) for n, s, e in spans.spans]
        traced = trace_mod.reduce(loaded, on_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        programs = system.live_programs(dev)

    # -- free the program, then the reference -----------------------------
    del api
    gc.collect()
    t = time.perf_counter()
    ref_out = round_ref.follow(
        ref, model_cfg, cell, feed, args.seed, followed,
        client_block=int(model_cfg.get("reference_client_block", 32)),
    )
    reference_s = time.perf_counter() - t
    nums = compare.numbers(prog, ref_out)
    # the work the rates count is the feed's: the program has to have placed
    # exactly those real samples in the window
    nums["placed_samples_gap"] = abs((placed[1] if placed else 0.0) * feed.epochs - real)
    correct, compared = compare.decide(nums, limits, failed) if limits else (None, [])
    if args.rehearse:
        return {
            "rehearsal": True, "workload": args.workload, "correct": correct,
            "rounds_in_window": n_rounds, "evals_in_window": rounds["evals_in_window"],
            "attempted": attempted, "failed": failed, "real_samples": real,
            "placed": placed, "stretches": len(anatomy["stretches_s"]),
            "compiles_in_window": in_window["compiles"], "shape_classes": len(seen),
            "schedule": schedule, "compared": compared,
        }

    run = {
        "rounds": n_rounds, "elapsed_s": elapsed, "setup_s": setup_s,
        "real_samples": real, "units": units, "unit_name": unit_name,
        "placed": placed, "program_spans": program_spans, "bench_spans": spans.spans,
        "trace": traced, "to_trace_ns": lambda us: us * 1e3 + offset_ns,
        "covered": trace_mod.covered, "compiles_in_window": in_window["compiles"],
        "memory_stats": memory_stats, "programs": programs,
        "peaks": device_peaks, "chips": chips,
    }
    if args.trace:
        unit_shapes = ref.unit_batch(model_cfg)
        shapes0 = {k: jax.ShapeDtypeStruct(v, "float32")
                   for k, v in ref.param_shapes(model_cfg).items()}

        def unit_loss(p, x, y):
            m = jax.numpy.ones((x.shape[0],), jax.numpy.float32)
            return round_ref.task_loss(
                model_cfg["task"], ref.logits_fn(p, x, round_ref.REFERENCE, model_cfg), y, m)[0]

        run["flops_per_unit"] = flops.fn_flops(
            jax.grad(unit_loss), shapes0, *unit_shapes) / feed.units_per_sample
    metrics = {}
    for m in metrics_for(bench, args.workload, "per_layer" if args.trace else "end_to_end"):
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
        "memory_peak_bytes": int(hbm_peak),
    }
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if traced is not None:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    result["run"] = {
        "workload": args.workload, "seed": args.seed, "window_rounds": n_rounds,
        "window_s": elapsed, "reference_s": reference_s, "schedule": schedule,
        "compile_cache": cache_dir, "compiles_in_window": in_window["compiles"],
        "worst_leaves": {k: v for k, v in nums.items() if k.endswith("_leaf")},
        "memory_stats": memory_stats, "programs": programs[:8] if programs else None,
        "process_cpu_s": cpu_s, **anatomy,
        "slowest_rounds": window.slowest_rounds(program_spans, tracer_t0),
        "sleeper": sleeper.reading(),
    }
    result["compared"] = compared
    return result


def main(argv=None):
    result = measure(argv)
    if result is None:
        return 0
    print("compared " + " ".join(f"{n}={v:.6g}<={lim:g}" for n, v, lim in result["compared"]),
          file=sys.stderr, flush=True)
    if result.get("rehearsal"):
        print("rehearsal " + json.dumps(result))
        print("rehearsal only: counts, no rate, no result line", file=sys.stderr)
        return 0
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
