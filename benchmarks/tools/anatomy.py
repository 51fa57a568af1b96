"""Which layer each device operation belongs to, and how far the harness's
clock join is off: one traced window of a cell, with the trace kept.

    python3 benchmarks/tools/anatomy.py --workload <cell> [--seed 1] [--out FILE]

The window is the traced window of ``run.py`` (same rounds, same spans, same
profiler options). From its trace:

- seconds of device time per named scope (``jax.named_scope`` in the program:
  ``local_train``, ``forward_backward``, ``optimizer_update``, ``keep_gate``,
  ``aggregate``, ``round_metrics``, ``gather``, ``mask_pad``, ``eval``) and,
  beneath ``forward_backward``, per layer of the model (its flax modules),
  forward and backward apart; the share of the round program's device time
  that carries a scope;
- the twenty largest device operations, each with its ``op_name``. The TPU
  trace's events carry their HLO instruction as a name and only timing stats
  (``device_offset_ps``, ``device_duration_ps``), no metadata, so the
  ``op_name`` is looked up by instruction and result type in the HLO text of
  the executables the process holds loaded;
- seconds of the window per host span of the program (count, total, self
  time), and the time no span covers;
- the window's idle gaps by the host span open when each began, once with
  the spans joined by ``offset_ns`` and once by the annotations below;
- the skew between every ``fedml.<span>`` annotation the program mirrors into
  the trace and the same span put through the harness's ``offset_ns`` (the
  window mark's start less the tracer's clock read before it): what the join
  of the two clocks that ``run.py`` makes is off by.

It also times the tracer itself on this host: microseconds per span with the
annotation hook installed, and per bare annotation, while no profile runs.

One JSON document on standard output (and in ``--out``); a readable table on
standard error. Needs a TPU unless ``--rehearse`` (then only the host side:
the skew and the span cost; the CPU's trace has no device plane)."""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import re
import shutil
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

SCOPES = ("local_train", "forward_backward", "optimizer_update", "keep_gate", "aggregate",
          "round_metrics", "gather", "mask_pad", "eval")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\S+) .*?op_name=\"([^\"]*)\"", re.M)


def scope_of(op_name: str) -> str:
    """The chain of the program's named scopes in an ``op_name``, outermost
    first ("local_train/forward_backward"), or "" where it holds none."""
    # the last part is the primitive (a ``gather`` there is jnp.take, no scope)
    parts = re.split(r"[/()]", (op_name or "").rpartition("/")[0])
    chain = []
    for p in parts:
        if p in SCOPES and p not in chain:
            chain.append(p)
    return "/".join(chain)


def layer_of(op_name: str) -> str:
    """The model's layer in an ``op_name`` beneath ``forward_backward``: the
    flax module path after ``jvp(<Model>)``, block numbers folded, with
    ``bwd:`` in front where the operation is the transposed (backward) one."""
    m = re.search(r"jvp\((\w+)\)\)?/(.*)", op_name or "")
    if not m or not m.group(1):
        return ""
    path = m.group(2).split("/")[:-1]  # the last part is the primitive
    path = [re.sub(r"^(block|layer|Block_|layers_)\d+$", r"\1*", p) for p in path
            if not re.match(r"^(jit|vmap|pjit|jvp|transpose|remat|checkpoint|custom_jvp|custom_vjp)\b", p)]
    where = "/".join(path[:2]) or "(model)"
    return ("bwd:" if "transpose(" in op_name else "fwd:") + where


def result_type(text: str) -> str:
    """An instruction's result type without its layout: f32[20,512]."""
    return text.split("{")[0]


def hlo_op_names(device) -> dict:
    """{program name: {(instruction, result type): op_name}} from the HLO text
    of every executable the process holds loaded. Variants of one program (a
    shape class each) number their instructions apart; where two disagree on
    an op_name under one key, the key maps to None."""
    out: dict = {}
    for exe in device.client.live_executables():
        for mod in exe.hlo_modules():
            table = out.setdefault(mod.name, {})
            for inst, rtype, op_name in _INSTRUCTION.findall(mod.to_string()):
                key = (inst, result_type(rtype))
                if table.setdefault(key, op_name) != op_name:
                    table[key] = None
    return out


def read_trace(path: str) -> dict:
    """The XLA Ops and XLA Modules lines of the first TPU plane as plain
    lists, and the host plane's ``bench.*`` and ``fedml.*`` annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name and not ops:
            for ln in plane.lines:
                if ln.name == "XLA Modules":
                    modules = [(ev.name, ev.start_ns, ev.duration_ns) for ev in ln.events]
                elif ln.name == "XLA Ops":
                    ops = [(ev.name, ev.start_ns, ev.duration_ns) for ev in ln.events]
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(("bench.", "fedml.")):
                        host.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return {"ops": ops, "modules": modules, "host": host}


def device_anatomy(loaded: dict, lo: float, hi: float, hlo: dict) -> dict:
    """Per program of the window: seconds per scope chain, per model layer
    beneath ``forward_backward``, the share that carries a scope; and the
    twenty largest operations with their op_name."""
    import numpy as np

    from benchmarks.lib.trace import _CONTAINERS  # operations that only span others

    mods = sorted(loaded["modules"], key=lambda m: m[1])
    starts = np.asarray([m[1] for m in mods], float)
    ends = np.asarray([m[1] + m[2] for m in mods], float)
    per_op: dict = {}  # (program, instruction) -> [seconds, count, event name]
    for name, start, dur in loaded["ops"]:
        if _CONTAINERS.match(name):
            continue
        sec = (min(start + dur, hi) - max(start, lo)) / 1e9
        if sec <= 0:
            continue
        i = int(np.searchsorted(starts, start, side="right")) - 1
        prog = re.sub(r"\(.*$", "", mods[i][0]) if i >= 0 and start < ends[i] else "?"
        row = per_op.setdefault((prog, name), [0.0, 0, name])
        row[0] += sec
        row[1] += 1
    programs: dict = {}
    rows = []
    for (prog, name), (sec, count, _) in per_op.items():
        inst, _, rest = name.partition(" = ")
        inst, shape = inst.lstrip("%"), result_type(rest.split(" ")[0])
        op_name = hlo.get(prog, {}).get((inst, shape)) or ""
        scope = scope_of(op_name)
        p = programs.setdefault(prog, {"seconds": 0.0, "scoped_s": 0.0, "scopes": {}, "layers": {}})
        p["seconds"] += sec
        p["scopes"][scope or "(no scope)"] = p["scopes"].get(scope or "(no scope)", 0.0) + sec
        if scope:
            p["scoped_s"] += sec
        if "forward_backward" in scope:
            layer = layer_of(op_name) or "(outside the model)"
            p["layers"][layer] = p["layers"].get(layer, 0.0) + sec
        rows.append({"program": prog, "op": inst, "shape": shape, "seconds": sec, "count": count,
                     "scope": scope, "op_name": op_name[-220:]})
    for p in programs.values():
        p["scoped_pct"] = 100.0 * p["scoped_s"] / p["seconds"] if p["seconds"] else None
        p["scopes"] = dict(sorted(p["scopes"].items(), key=lambda kv: -kv[1]))
        p["layers"] = dict(sorted(p["layers"].items(), key=lambda kv: -kv[1]))
    rows.sort(key=lambda r: -r["seconds"])
    return {"programs": dict(sorted(programs.items(), key=lambda kv: -kv[1]["seconds"])),
            "largest_ops": rows[:20]}


def clock_skew(host: list, program_spans: list, lo: float, offset_ns: float) -> dict:
    """Microseconds between each ``fedml.<name>`` annotation's start on the
    trace's clock and the same span's start put through ``offset_ns``
    (annotation less prediction), the k-th annotation of a name against the
    k-th span of that name in the window."""
    mirrored: dict = {}
    for name, start, _ in sorted(host, key=lambda h: h[1]):
        if name.startswith("fedml.") and start >= lo:
            mirrored.setdefault(name[len("fedml."):], []).append(start)
    by_name, every = {}, []
    for name, starts in mirrored.items():
        spans = sorted(s for n, s, _, _ in program_spans if n == name)
        if len(spans) != len(starts):
            by_name[name] = {"annotations": len(starts), "spans": len(spans), "unmatched": True}
            continue
        skews = [(a - (s * 1e3 + offset_ns)) / 1e3 for a, s in zip(starts, spans)]
        every += skews
        by_name[name] = {"n": len(skews), "median_us": statistics.median(skews),
                         "min_us": min(skews), "max_us": max(skews)}
    if not every:
        return {"n": 0, "by_name": by_name}
    return {"n": len(every), "median_us": statistics.median(every),
            "largest_us": max(every, key=abs), "by_name": by_name}


def host_anatomy(program_spans: list, elapsed_s: float) -> dict:
    """Seconds of the window per span name: how many, their total, their
    self time (less the spans directly beneath, by ``parent`` and
    containment), and ``(unspanned)``: the window less its depth-0 spans."""
    spans = sorted(program_spans, key=lambda s: (s[1], -s[2]))
    out: dict = {}
    open_: list = []  # the nesting stack, as the tracer had it
    for name, start, end, attrs in spans:
        while open_ and open_[-1][1] <= start:
            open_.pop()
        row = out.setdefault(name, {"n": 0, "total_s": 0.0, "self_s": 0.0})
        row["n"] += 1
        row["total_s"] += (end - start) / 1e6
        row["self_s"] += (end - start) / 1e6
        if open_ and attrs.get("parent") == open_[-1][0]:
            out[open_[-1][0]]["self_s"] -= (end - start) / 1e6
        open_.append((name, end))
    top = sum(e - s for _, s, e, a in spans if a.get("depth") == 0) / 1e6
    out["(unspanned)"] = {"n": 0, "total_s": elapsed_s - top, "self_s": elapsed_s - top}
    return out


def idle_anatomy(loaded: dict, program_spans: list, lo: float, hi: float,
                 offset_ns: float) -> dict:
    """The window's idle gaps by the host span open when each began, twice:
    with the program's spans put through ``offset_ns`` (what ``run.py``
    reports as ``breakdown.idle_gaps``) and with the ``fedml.*`` annotations,
    which are on the trace's own clock; ``round`` left out both times, as
    ``run.py`` leaves it out. And the five longest gaps with their owner each
    way: [ms into the window, ms long, by offset, by annotation]."""
    from benchmarks.lib import trace as trace_mod

    merged = trace_mod.merge([o[1] for o in loaded["ops"]], [o[2] for o in loaded["ops"]])
    gap_list = trace_mod.gaps(merged, lo, hi)
    joined = [(n, s * 1e3 + offset_ns, e * 1e3 + offset_ns)
              for n, s, e, _ in program_spans if n != "round"]
    mirrored = [(n[len("fedml."):], s, e) for n, s, e in loaded["host"]
                if n.startswith("fedml.") and n != "fedml.round"]

    def owner(gap, spans):
        return next(iter(trace_mod.attribute_gaps([gap], spans)))

    longest = sorted(gap_list, key=lambda g: g[0] - g[1])[:5]
    return {
        "idle_s": sum(b - a for a, b in gap_list) / 1e9, "gaps": len(gap_list),
        "by_offset": trace_mod.attribute_gaps(gap_list, joined),
        "by_annotation": trace_mod.attribute_gaps(gap_list, mirrored),
        "longest": [[(a - lo) / 1e6, (b - a) / 1e6, owner((a, b), joined), owner((a, b), mirrored)]
                    for a, b in longest],
    }


def span_cost(n: int = 20000) -> dict:
    """Microseconds per span of a fresh tracer with the program's annotation
    hook installed, per span without it, and per bare annotation, while no
    profile runs: the best of three rounds of ``n``."""
    import jax

    from fedml_tpu.telemetry.spans import Tracer
    from fedml_tpu.utils.profiling import span_annotation

    def spans(tracer):
        t = time.perf_counter()
        for i in range(n):
            with tracer.span("stack", round=i) as sp:
                sp.set_attr("steps", 3)
        return (time.perf_counter() - t) / n * 1e6

    def bare():
        t = time.perf_counter()
        for i in range(n):
            with jax.profiler.TraceAnnotation("fedml.stack", round=i):
                pass
        return (time.perf_counter() - t) / n * 1e6

    def hooked():
        tracer = Tracer()
        tracer.annotate = span_annotation
        return spans(tracer)

    return {"span_with_hook_us": min(hooked() for _ in range(3)),
            "span_without_hook_us": min(spans(Tracer()) for _ in range(3)),
            "bare_annotation_us": min(bare() for _ in range(3))}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None, help="also write the JSON document here")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from benchmarks import run
    from benchmarks.lib import feed as feed_mod, system, trace as trace_mod, window

    bench = run.load_json(ROOT / "BENCHMARK.json")
    _, model_cfg, cell, _, ref = run.load_cell(bench, args.workload, args.rehearse)
    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        run.fail("needs a TPU (or --rehearse)", 3)
    system.install_compile_cache()
    plan = window.plan(float(bench["run_seconds"]), float(cell["nominal_rounds_per_s"]),
                       int(cell["eval_every"]), window.FOLLOWED, int(cell["trace_eval_periods"]))
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
    trace_dir = run.WORK / f"anatomy-{args.workload}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    gc.collect()
    gc.freeze()
    # the window exactly as run.py opens it: the tracer's clock is read, then
    # the sleeper's thread starts, then the mark opens
    tracer_t0 = tracer.now_us()
    with window.Sleeper(), jax.profiler.TraceAnnotation(trace_mod.WINDOW_MARK):
        t0 = time.perf_counter()
        system.run_rounds(api, w0, w1)
        elapsed = time.perf_counter() - t0
    jax.profiler.stop_trace()
    gc.unfreeze()

    program_spans = system.program_spans(tracer, tracer_t0)
    loaded = read_trace(trace_mod.find_xplane(str(trace_dir)))
    lo, hi = trace_mod.window_of([h for h in loaded["host"] if h[0].startswith("bench.")])
    offset_ns = lo - tracer_t0 * 1e3
    out = {
        "workload": args.workload, "seed": args.seed, "rounds": w1 - w0, "window_s": elapsed,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "trace": str(trace_dir.relative_to(ROOT)),
        "skew": clock_skew(loaded["host"], program_spans, lo, offset_ns),
        "host_spans": host_anatomy(program_spans, elapsed),
        "span_cost": span_cost(),
        "spans_in_window": len(program_spans),
    }
    if loaded["ops"]:
        out["idle"] = idle_anatomy(loaded, program_spans, lo, hi, offset_ns)
        out.update(device_anatomy(loaded, lo, hi, hlo_op_names(dev)))
    text = json.dumps(out)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(text + "\n")
    report(out)
    print(text, flush=True)
    return 0


def report(out: dict):
    err = sys.stderr
    print(f"== {out['workload']}: {out['rounds']} rounds, {out['window_s']:.3f} s", file=err)
    for prog, p in out.get("programs", {}).items():
        print(f"-- {prog}: {p['seconds']:.4f} s, {p['scoped_pct']:.2f} % under a named scope", file=err)
        for scope, sec in p["scopes"].items():
            print(f"   {sec:9.4f} s  {100 * sec / p['seconds']:6.2f} %  {scope}", file=err)
        for layer, sec in list(p["layers"].items())[:16]:
            print(f"     {sec:9.4f} s  {layer}", file=err)
    for r in out.get("largest_ops", []):
        print(f"   {r['seconds']:8.4f} s x{r['count']:<5} {r['program']}:{r['op']} {r['shape']}  "
              f"[{r['scope'] or '-'}] {r['op_name'][-110:]}", file=err)
    for name, row in out["host_spans"].items():
        print(f"   host {name:<12} x{row['n']:<5} total {row['total_s']:9.4f} s  "
              f"self {row['self_s']:9.4f} s  ({1e3 * row['self_s'] / out['rounds']:.3f} ms a round)",
              file=err)
    if "idle" in out:
        idle = out["idle"]
        print(f"-- idle {idle['idle_s']:.4f} s in {idle['gaps']} gaps; by the span open when each "
              f"began, spans joined by offset_ns: {idle['by_offset']}", file=err)
        print(f"   the same by the fedml.* annotations (the trace's clock): {idle['by_annotation']}",
              file=err)
        for at, ms, by_off, by_ann in idle["longest"]:
            print(f"   gap at {at:10.3f} ms, {ms:8.3f} ms long: {by_off} / {by_ann}", file=err)
    sk = out["skew"]
    if sk["n"]:
        print(f"-- skew of {sk['n']} fedml.* annotations against offset_ns: median "
              f"{sk['median_us']:.2f} us, largest {sk['largest_us']:.2f} us", file=err)
    print(f"-- span cost: {out['span_cost']}", file=err)


if __name__ == "__main__":
    raise SystemExit(main())
