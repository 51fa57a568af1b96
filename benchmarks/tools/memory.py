"""What a cell's chip holds, pool by pool: the runtime's ``memory_stats()``
after the API is built, after the followed rounds and after a warm-up
period; every loaded program with the temporaries the compiler planned for
it; and whether buffers of growing size can still be allocated, which says
whether the space the runtime reserved for the programs is held or free.

    python3 benchmarks/tools/memory.py --workload <cell> [--seed 1]

One JSON line per stage on standard output. Needs a TPU unless
``--rehearse``."""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved", "peak_bytes_reserved",
        "bytes_limit", "bytes_reservable_limit", "largest_free_block_bytes")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks import run
    from benchmarks.lib import feed as feed_mod, system, window

    bench = run.load_json(ROOT / "BENCHMARK.json")
    _, model_cfg, cell, _, ref = run.load_cell(bench, args.workload, args.rehearse)
    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        run.fail("needs a TPU (or --rehearse)", 3)
    system.install_compile_cache()

    def stage(name, **more):
        stats = dev.memory_stats() or {}
        print(json.dumps({"stage": name, **{k: stats.get(k) for k in KEYS}, **more}), flush=True)

    plan = window.plan(1.0, float(cell["nominal_rounds_per_s"]), int(cell["eval_every"]))
    feed = feed_mod.Feed(model_cfg, cell, args.seed)
    api = system.build(model_cfg, cell, feed, args.seed, ref, [])
    jax.block_until_ready(api.global_vars)
    stage("api built, population placed")
    system.follow(api, ref.init_params(args.seed, model_cfg), window.FOLLOWED)
    stage("followed rounds")
    system.run_rounds(api, *plan["warm"])
    stage("one evaluation period", programs=system.live_programs(dev)[:10])
    held = []
    for gib in (0.5, 1, 2, 4, 8):
        try:
            held.append(jax.block_until_ready(jnp.zeros((int(gib * 2**30) // 4,), jnp.float32)))
            stage(f"allocated a further {gib} GiB buffer")
        except Exception as e:  # noqa: BLE001 - the refusal is the reading
            stage(f"a further {gib} GiB buffer is refused", error=str(e).splitlines()[0][:300])
            break
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
