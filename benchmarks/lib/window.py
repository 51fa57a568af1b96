"""Window arithmetic: which rounds a run measures, and the rates.

The window is ONE call of ``train()`` over rounds [start, start + N]: N is a
multiple of the cell's evaluation cadence and start is one too, so the
window opens on an evaluation round, closes on one, and every window holds
N / cadence + 1 evaluations. N is fixed by the cell (its nominal rate, read
once on the chip) and ``--seconds``, never by a clock inside the run: every
run of a cell does the same rounds, so a rate is those rounds over the time
they took, and has no quantum."""

from __future__ import annotations

import math
import threading
import time

# Rounds that ``correct`` follows from the seed, in every cell (the
# reference follows the same ones).
FOLLOWED = 3


def window_rounds(seconds: float, nominal_rounds_per_s: float, cadence: int) -> int:
    """N: the multiple of ``cadence`` (one at least) whose rounds last
    nearest to ``seconds`` at the cell's nominal rate, so that a window is
    about ``seconds`` long: within half an evaluation period of it."""
    if seconds <= 0 or nominal_rounds_per_s <= 0 or cadence <= 0:
        raise ValueError("seconds, nominal rate and cadence must be positive")
    return cadence * max(1, math.floor(seconds * nominal_rounds_per_s / cadence + 0.5))


def plan(seconds, nominal_rounds_per_s, cadence, followed=FOLLOWED, trace_periods=0):
    """Rounds of a run. The followed rounds [0, followed) feed ``correct``;
    the steady warm-up is a one-period window of its own, [warm, warm +
    cadence]; the measured window follows it at the next multiple of the
    cadence. A traced run measures ``trace_periods`` periods instead."""
    n = window_rounds(seconds, nominal_rounds_per_s, cadence)
    if trace_periods:
        n = min(n, cadence * trace_periods)
    warm = cadence * math.ceil(followed / cadence)
    start = warm + 2 * cadence
    return {
        "followed": (0, followed),
        "warm": (warm, warm + cadence + 1),
        "window": (start, start + n + 1),
        "evals_in_window": n // cadence + 1,
    }


def periods(t0: float, flush_ends: list) -> list:
    """Seconds of each stretch of a window that ends with a flush: where a
    window's time went, stretch by stretch (a stall shows as one long
    stretch, a slower device as all of them longer)."""
    edges = [t0] + list(flush_ends)
    return [b - a for a, b in zip(edges, edges[1:])]


def flush_anatomy(t0_us: float, spans: list) -> dict:
    """From the benchmark's spans (name, start_us, end_us) of one window:
    ``stretches_s`` (see ``periods``) and ``flush_waits_s``, the wait for
    the device inside each flush, from its start to its first logged row.
    A flush that logged nothing (the one ``train()`` ends with) is left out."""
    logs = sorted(s for n, s, _ in spans if n == "bench.log")
    ends, waits = [], []
    for n, s, e in spans:
        if n != "bench.flush":
            continue
        first = next((l for l in logs if s <= l <= e), None)
        if first is not None:
            ends.append(e / 1e6)
            waits.append((first - s) / 1e6)
    return {"stretches_s": periods(t0_us / 1e6, ends), "flush_waits_s": waits}


def slowest_rounds(program_spans: list, t0_us: float = 0.0, k: int = 3) -> dict:
    """From the program's spans (name, start_us, end_us, attrs): the seconds
    from each round's dispatch (its ``local_train`` span) to the next one's,
    the median of them and the ``k`` longest as [round, seconds, seconds
    into the window at which it was dispatched]. The loop blocks on the
    device within a few rounds, so a round that ran long on the device
    shows here a few rounds later."""
    starts = sorted((s, a.get("round", -1)) for n, s, _, a in program_spans if n == "local_train")
    gaps = sorted(((b[0] - a[0]) / 1e6, a[1], (a[0] - t0_us) / 1e6) for a, b in zip(starts, starts[1:]))
    if not gaps:
        return {}
    return {"median_s": gaps[len(gaps) // 2][0],
            "longest": [[r, g, at] for g, r, at in reversed(gaps[-k:])]}


class Sleeper:
    """A thread that sleeps ``step`` seconds over and over and books every
    wake-up that came more than ``late`` seconds late: how many, how long
    together, the longest and when. The loop's blocking calls release the
    interpreter lock, so while the main thread merely waits for the device
    the sleeper wakes on time; it is late only when the whole process was
    held up (on the chip's machine: about 0.1 s, a few times in 40 s; the
    slowest rounds of a run start at those moments). Twenty wake-ups a
    second: it costs the window nothing that can be measured."""

    def __init__(self, step: float = 0.05, late: float = 0.02):
        self.step, self.late = step, late
        self.worst, self.at, self.n, self.total = 0.0, 0.0, 0, 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        t0 = last = time.perf_counter()
        while not self._stop.wait(self.step):
            now = time.perf_counter()
            over = now - last - self.step
            if over > self.late:
                self.n += 1
                self.total += over
            if over > self.worst:
                self.worst, self.at = over, last - t0
            last = now

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()

    def reading(self) -> dict:
        return {"overslept_s": self.worst, "at_s": self.at,
                "late_n": self.n, "late_s": self.total}
