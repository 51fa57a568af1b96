"""Seconds of the set-up inside XLA compiles: the program's ``jit_backend``
spans before the window whose ``cache`` is not ``hit``, summed. 0.0 in a
warm set-up."""

from benchmarks.lib import setup_spans as lib


def read(run):
    spans = lib.jit_setup_spans(run)
    return None if spans is None else float(sum(lib.backends(spans, hit=False)))
