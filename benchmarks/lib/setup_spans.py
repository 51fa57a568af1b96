"""What the program's own tracer holds from BEFORE the window: the spans of
its set-up, ``api_init`` and ``store_upload``, the followed and warm-up
rounds, and the ``jit_trace`` / ``jit_lower`` / ``jit_backend`` spans that
its compile listener leaves under whichever span paid for a program's
first call (``fedml_tpu/analysis/sentinel.py``). The six ``setup.*``
readers and ``compile.window_programs`` read through here.

``run["program_spans"]`` starts at the window, so the earlier events come
from the tracer itself (process-wide, never reset: the one ``run.py`` reads)
until a ``benchmark`` issue hands them over as ``run["setup_spans"]``, which
is read first where it is there. A program from before the listener leaves
no ``jit_*`` span anywhere: every reader but the ``store_upload`` one then
reads nothing."""

from __future__ import annotations

from . import trace

JIT = ("jit_trace", "jit_lower", "jit_backend")


def setup_spans(run) -> list:
    """(name, start_us, end_us, attrs) of the program's spans that started
    before the window's first; empty where the window has no span to tell
    its start by."""
    spans = run.get("setup_spans")
    if spans is None:
        from . import system

        spans = system.program_spans(system.get_tracer(), 0.0)
    window_us = min((s for _, s, _, _ in run["program_spans"]), default=None)
    if window_us is None:
        return []
    return [sp for sp in spans if sp[1] < window_us]


def jit_setup_spans(run):
    """``setup_spans`` where the program spans its compile path, else None."""
    spans = setup_spans(run)
    return spans if any(n in JIT for n, *_ in spans) else None


def backends(spans, hit: bool) -> list:
    """Seconds of each ``jit_backend`` that was (``hit``) or was not loaded
    from the persistent cache: a ``miss`` was compiled and written, an
    ``off`` compiled and not written."""
    return [
        (e - s) / 1e6 for n, s, e, a in spans
        if n == "jit_backend" and (a.get("cache") == "hit") == hit
    ]


def union_s(intervals) -> float:
    """Seconds covered by the (start_us, end_us) intervals: inner jits trace
    inside the outer trace's interval, so durations are never summed."""
    intervals = list(intervals)
    lo, hi = trace.merge([s for s, _ in intervals], [e - s for s, e in intervals])
    return float((hi - lo).sum()) / 1e6


def payers(spans) -> list:
    """The span instances that paid for a program's first call: for every
    ``jit_backend`` with a ``parent``, the innermost span of that name
    round its end; each once, as (start_us, end_us)."""
    found = set()
    for n, _, end, a in spans:
        if n != "jit_backend" or "parent" not in a:
            continue
        around = [(s, e) for m, s, e, _ in spans if m == a["parent"] and s <= end <= e]
        if around:
            found.add(max(around))
    return sorted(found)
