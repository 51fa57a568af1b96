"""The comparison that decides ``correct``: the program's first followed
rounds against the plain reference's, number by number, each under a limit
of its own (benchmarks/limits/<cell>.json, set from readings on the chip)."""

from __future__ import annotations

import math
import statistics

# A leaf whose first change in the reference is under this share of the
# median leaf's is nought to rounding there, and is left out of ``change``.
NOUGHT_SHARE = 1e-3


def rel_gap(p: float, r: float) -> float:
    if not (math.isfinite(p) and math.isfinite(r)):
        return math.inf
    return abs(p - r) / max(abs(r), 1e-30)


def worst_leaf_gap(prog: dict, ref: dict, leaves=None):
    """Largest gap between the program's norm and the reference's over the
    leaves, measured against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Returns (gap, leaf)."""
    if set(prog) != set(ref):
        return math.inf, "leaf sets differ"
    med = statistics.median(ref.values())
    worst, at = 0.0, ""
    for k in (leaves if leaves is not None else ref):
        p, r = prog[k], ref[k]
        if not (math.isfinite(p) and math.isfinite(r)):
            return math.inf, k
        gap = abs(p - r) / max(r, med, 1e-30)
        if gap > worst:
            worst, at = gap, k
    return worst, at


def moving_leaves(ref_first: dict):
    med = statistics.median(ref_first.values())
    return [k for k, v in ref_first.items() if v >= NOUGHT_SHARE * med]


def numbers(prog: dict, ref: dict) -> dict:
    """Each number compared, by a short plain name. ``prog`` and ``ref``
    hold ``loss`` (per followed round), ``eval`` {round: (loss, acc)},
    ``norms_first`` and ``norms_last`` {leaf: norm}."""
    out = {}
    rounds = len(ref["loss"])
    for i in range(rounds):
        p = prog["loss"][i] if i < len(prog["loss"]) else math.nan
        out[f"loss_r{i}"] = rel_gap(p, ref["loss"][i])
    gaps = [
        rel_gap(prog["eval"].get(r, (math.nan,))[0], loss)
        for r, (loss, _) in ref["eval"].items()
    ]
    out["eval_loss"] = max(gaps) if gaps else math.inf
    out["first_change"], out["first_change_leaf"] = worst_leaf_gap(
        prog["norms_first"], ref["norms_first"])
    out["change"], out["change_leaf"] = worst_leaf_gap(
        prog["norms_last"], ref["norms_last"], moving_leaves(ref["norms_first"]))
    return out


# Exact comparisons: their limit is 0 in every cell.
EXACT = ("placed_samples_gap",)


def decide(nums: dict, limits: dict, window_faults: int):
    """(correct, [[name, value, limit], ...]). A number with no limit in the
    cell's file is an error: the cell has not been calibrated."""
    rows = []
    ok = True
    for name, value in nums.items():
        if name.endswith("_leaf"):
            continue
        if name in EXACT:
            rows.append([name, value, 0])
            ok = ok and value == 0
            continue
        key = "loss" if name.startswith("loss_r") else name
        if key not in limits:
            raise KeyError(f"no limit for {key!r} in the cell's limits file")
        lim = float(limits[key])
        rows.append([name, value, lim])
        ok = ok and value <= lim
    rows.append(["window_faults", window_faults, 0])
    return ok and window_faults == 0, rows
