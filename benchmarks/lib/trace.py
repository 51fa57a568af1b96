"""From a profiler trace to device numbers: busy and idle time, device time
per program and per operation, and each idle gap's host-side cause.

``load`` turns an ``.xplane.pb`` into plain arrays once; everything else
works on those arrays, so the reduction can be checked on a synthetic trace
(tests/test_selfcheck.py). All times are nanoseconds on the trace's clock."""

from __future__ import annotations

import glob
import os
import re

import numpy as np

WINDOW_MARK = "bench.window"


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def _plain(name: str) -> str:
    """An operation's name as the ledger carries it: the HLO name and its
    result type, with everything but letters, digits, '.', '_' folded."""
    head = name.split(" = ")
    short = head[0].lstrip("%")
    if len(head) > 1:
        short += "_" + head[1].split(" ")[0].split("{")[0]
    return re.sub(r"[^A-Za-z0-9._]+", "_", short).strip("_")[:80]


def load(path: str, host_prefix: str = "bench.") -> dict:
    """{"chips": [{"ops": (names, ids, start, dur), "modules": ...}, ...],
    "host": [(name, start, end), ...]}: the XLA Ops and XLA Modules lines of
    every TPU plane, and the host annotations named ``host_prefix``*."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    chips, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in plane.name:
            chip = {}
            for ln in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(ln.name)
                if key is None:
                    continue
                names, ids, start, dur, seen = [], [], [], [], {}
                for ev in ln.events:
                    n = ev.name
                    i = seen.get(n)
                    if i is None:
                        i = seen[n] = len(names)
                        names.append(n)
                    ids.append(i)
                    start.append(ev.start_ns)
                    dur.append(ev.duration_ns)
                chip[key] = (
                    names, np.asarray(ids, np.int64),
                    np.asarray(start, np.float64), np.asarray(dur, np.float64),
                )
            if chip:
                chips.append(chip)
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(host_prefix):
                        host.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return {"chips": chips, "host": host}


def merge(start, dur):
    """Union of intervals as sorted, disjoint (lo, hi) arrays."""
    if len(start) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(start, kind="stable")
    lo, hi = np.asarray(start)[order], (np.asarray(start) + np.asarray(dur))[order]
    reach = np.maximum.accumulate(hi)
    new = np.concatenate([[True], lo[1:] > reach[:-1]])
    first = np.flatnonzero(new)
    last = np.concatenate([first[1:] - 1, [len(lo) - 1]])
    return lo[first], reach[last]


def covered(merged, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] that the merged intervals cover."""
    mlo, mhi = merged
    if len(mlo) == 0 or hi <= lo:
        return 0.0
    return float(np.sum(np.clip(np.minimum(mhi, hi) - np.maximum(mlo, lo), 0, None)))


def gaps(merged, lo: float, hi: float):
    """Idle intervals of [lo, hi]: [(start, end), ...]."""
    mlo, mhi = merged
    keep = (mhi > lo) & (mlo < hi)
    mlo, mhi = np.clip(mlo[keep], lo, hi), np.clip(mhi[keep], lo, hi)
    edges_lo = np.concatenate([[lo], mhi])
    edges_hi = np.concatenate([mlo, [hi]])
    return [(a, b) for a, b in zip(edges_lo, edges_hi) if b > a]


def window_of(host) -> tuple:
    marks = [(s, e) for n, s, e in host if n == WINDOW_MARK]
    if len(marks) != 1:
        raise ValueError(f"expected one {WINDOW_MARK} annotation, found {len(marks)}")
    return marks[0]


def by_name(line, lo: float, hi: float) -> dict:
    """Seconds per event name, events clipped to [lo, hi]."""
    names, ids, start, dur = line
    clipped = np.clip(np.minimum(start + dur, hi) - np.maximum(start, lo), 0, None)
    sums = np.bincount(ids, weights=clipped, minlength=len(names))
    return {names[i]: float(sums[i]) / 1e9 for i in range(len(names)) if sums[i] > 0}


def attribute_gaps(gap_list, spans) -> dict:
    """Seconds of idle time by the host span open when each gap began: the
    one that started last, or ``no_span_open``. ``spans`` are (name, start,
    end) on the trace's clock."""
    out: dict = {}
    spans = sorted(spans, key=lambda s: s[1])
    for a, b in gap_list:
        owner = "no_span_open"
        for name, s, e in spans:
            if s > a:
                break
            if e >= a:
                owner = name
        out[owner] = out.get(owner, 0.0) + (b - a) / 1e9
    return out


# Container operations: their events span the operations nested in them.
_CONTAINERS = re.compile(r"^%?(while|conditional|call)[._ ]")


def reduce(loaded: dict, spans=()) -> dict:
    """The traced window's device numbers. ``spans`` are host spans on the
    trace's clock, for the idle gaps' causes."""
    lo, hi = window_of(loaded["host"])
    if not loaded["chips"]:
        raise ValueError("the trace holds no TPU plane with XLA Ops")
    busy, programs, ops, merged_all = [], {}, {}, []
    for chip in loaded["chips"]:
        line = chip.get("ops") or chip["modules"]
        merged = merge(line[2], line[3])
        merged_all.append(merged)
        busy.append(covered(merged, lo, hi) / 1e9)
        for k, v in by_name(chip["modules"], lo, hi).items() if "modules" in chip else ():
            programs[k] = programs.get(k, 0.0) + v
        for k, v in by_name(line, lo, hi).items():
            if not _CONTAINERS.match(k):
                ops[_plain(k)] = ops.get(_plain(k), 0.0) + v
    n = len(loaded["chips"])
    top = sorted(ops.items(), key=lambda kv: -kv[1])
    gap_list = gaps(merged_all[0], lo, hi)
    causes = attribute_gaps(gap_list, spans)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n,
        "window_ns": (lo, hi),
        "merged": merged_all[0],
        "programs": {k: v / n for k, v in programs.items()},
        "device_ops": [[k, v / n] for k, v in top[:10]],
        "idle_gaps": [[k, v] for k, v in sorted(causes.items(), key=lambda kv: -kv[1])[:10]],
    }
