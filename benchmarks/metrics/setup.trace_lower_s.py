"""Seconds of the set-up inside jax's tracing and lowering: the union of the
program's ``jit_trace`` and ``jit_lower`` spans before the window. Python
time of the program's own model and round code, paid by every process
whether the executable then comes from the cache or the compiler."""

from benchmarks.lib import setup_spans as lib


def read(run):
    spans = lib.jit_setup_spans(run)
    if spans is None:
        return None
    return lib.union_s((s, e) for n, s, e, _ in spans if n in ("jit_trace", "jit_lower"))
