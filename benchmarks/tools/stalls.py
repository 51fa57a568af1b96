"""Where a window's time goes when a run reads far off: many windows of one
cell in one process, each with its stretches (flush to flush), the wait
for the device inside each flush, and the rounds that took longest.

    python3 benchmarks/tools/stalls.py --workload <cell> --windows 8 --seconds 40

Set-up is paid once. One JSON line per window on standard output. The
windows drive the same rounds of the same ``train()`` object as ``run.py``
does, under the same spans. Needs a TPU unless ``--rehearse``."""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--windows", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--gc-off-odd", action="store_true",
                    help="odd-numbered windows run with the collector disabled")
    args = ap.parse_args(argv)

    import jax

    from benchmarks import run
    from benchmarks.lib import feed as feed_mod, system, window

    bench = run.load_json(ROOT / "BENCHMARK.json")
    _, model_cfg, cell, _, ref = run.load_cell(bench, args.workload, args.rehearse)
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        run.fail("needs a TPU (or --rehearse)", 3)
    system.install_compile_cache()
    counter = system.CompileCounter()
    plan = window.plan(args.seconds, float(cell["nominal_rounds_per_s"]), int(cell["eval_every"]))
    feed = feed_mod.Feed(model_cfg, cell, args.seed)
    api = system.build(model_cfg, cell, feed, args.seed, ref, [])
    system.follow(api, ref.init_params(args.seed, model_cfg), window.FOLLOWED)
    w0, w1 = plan["window"]
    system.run_rounds(api, *plan["warm"])
    seen = {feed.shape_class(r) for r in list(range(window.FOLLOWED)) + list(range(*plan["warm"]))}
    for r in range(w0, w1):
        if feed.shape_class(r) not in seen:
            seen.add(feed.shape_class(r))
            system.run_rounds(api, r, r + 1)
    tracer = system.get_tracer()
    spans = system.SpanLog(tracer)
    for method, name in (("_pipeline_prepare", "bench.prepare"), ("_flush_pending", "bench.flush"),
                         ("_log_round", "bench.log")):
        spans.wrap(api, method, name)
    gc.collect()
    gc.freeze()
    collections = []  # (generation, seconds) of every collection

    def on_gc(phase, info, _t=[0.0]):
        if phase == "start":
            _t[0] = time.perf_counter()
        else:
            collections.append((info["generation"], time.perf_counter() - _t[0]))

    gc.callbacks.append(on_gc)
    for i in range(args.windows):
        del spans.spans[:]
        del collections[:]
        if args.gc_off_odd and i % 2:
            gc.disable()
        mark = counter.mark()
        cpu0 = time.process_time()
        t0_us = tracer.now_us()
        with window.Sleeper() as sleeper:
            t0 = time.perf_counter()
            system.run_rounds(api, w0, w1)
            elapsed = time.perf_counter() - t0
        gc_on = gc.isenabled()
        gc.enable()
        print(json.dumps({
            "workload": args.workload, "window": i, "rounds": w1 - w0, "window_s": elapsed,
            "rounds_per_s": (w1 - w0) / elapsed,
            **window.flush_anatomy(t0_us, spans.spans),
            "slowest_rounds": window.slowest_rounds(system.program_spans(tracer, t0_us), t0_us),
            "sleeper": sleeper.reading(),
            "gc": {"enabled": gc_on, "collections": len(collections),
                   "full": sum(1 for g, _ in collections if g == 2),
                   "total_s": sum(t for _, t in collections),
                   "longest_s": max((t for _, t in collections), default=0.0)},
            "compiles": counter.since(mark)["compiles"],
            "process_cpu_s": time.process_time() - cpu0,
        }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
