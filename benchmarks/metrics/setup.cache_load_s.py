"""Seconds of the set-up inside ``compile_or_get_cached`` calls that the
persistent cache answered: the program's ``jit_backend`` spans with
``cache`` = ``hit`` before the window, summed (reading the entry,
deserialising it, loading the executable onto the device)."""

from benchmarks.lib import setup_spans as lib


def read(run):
    spans = lib.jit_setup_spans(run)
    return None if spans is None else float(sum(lib.backends(spans, hit=True)))
