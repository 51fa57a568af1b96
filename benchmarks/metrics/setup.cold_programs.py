"""Programs the set-up compiled: the program's ``jit_backend`` spans before
the window whose ``cache`` is not ``hit``. What tells a cold set-up from a
warm one on a ledger line: a ``setup_s`` pair whose sides differ here
differs by the cache's doing."""

from benchmarks.lib import setup_spans as lib


def read(run):
    spans = lib.jit_setup_spans(run)
    return None if spans is None else float(len(lib.backends(spans, hit=False)))
