"""Programs the window compiled or loaded, as the program itself saw them:
``jit_backend`` spans in ``run["program_spans"]`` (each names its
``program``, its ``round`` and the span that waited for it). The inside twin
of ``compile.in_window``. Expected 0; None where the program does not span
its compile path (no ``jit_*`` span before the window either)."""

from benchmarks.lib import setup_spans as lib


def read(run):
    if lib.jit_setup_spans(run) is None:
        return None
    return float(sum(1 for n, *_ in run["program_spans"] if n == "jit_backend"))
