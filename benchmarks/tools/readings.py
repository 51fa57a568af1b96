"""Readings for a cell's limits, several seeds in one process.

    python3 benchmarks/tools/readings.py --workload <cell> --seeds 1,2,3 \
        [--control] [--fault half_batch] [--rehearse]

For each seed: the program's followed rounds against the plain reference
(the lower reading), and on request the control (the reference in the
configuration's next lower precision, put in the program's place) and a
planted fault (the reference with half of every minibatch left out), each
against the same reference (the upper readings). One JSON line per seed on
standard output. Needs a TPU unless ``--rehearse``."""

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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from benchmarks import run
    from benchmarks.lib import compare, feed as feed_mod, system, window

    bench = run.load_json(ROOT / "BENCHMARK.json")
    _, model_cfg, cell, _, ref = run.load_cell(bench, args.workload, args.rehearse)
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        run.fail("needs a TPU (or --rehearse)", 3)
    system.install_compile_cache()
    followed = window.FOLLOWED
    fedavg_ref = system.load_round_reference(cell)
    block = int(model_cfg.get("reference_client_block", 32))

    for seed in (int(s) for s in args.seeds.split(",")):
        line = {"workload": args.workload, "seed": seed}
        feed = feed_mod.Feed(model_cfg, cell, seed)
        rows: list = []
        api = system.build(model_cfg, cell, feed, seed, ref, rows)
        first, last, _ = system.follow(api, ref.init_params(seed, model_cfg), followed)
        prog = run.program_reading(rows, followed, first, last)
        del api
        gc.collect()
        t = time.perf_counter()
        ref_out = fedavg_ref.follow(ref, model_cfg, cell, feed, seed, followed, client_block=block)
        line["reference_s"] = time.perf_counter() - t
        line["reference_loss"] = ref_out["loss"]
        line["program"] = compare.numbers(prog, ref_out)
        if args.control:
            ops = fedavg_ref.Ops(**model_cfg["precision"]["control_ops"])
            out = fedavg_ref.follow(ref, model_cfg, cell, feed, seed, followed, ops=ops,
                                    client_block=block)
            line["control"] = compare.numbers(out, ref_out)
        if args.fault:
            out = fedavg_ref.follow(ref, model_cfg, cell, feed, seed, followed,
                                    client_block=block, fault=args.fault)
            line["fault_" + args.fault] = compare.numbers(out, ref_out)
        print(json.dumps(line), flush=True)
        del feed
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
