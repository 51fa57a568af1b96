"""What first calls cost the set-up beyond tracing, lowering and compile or
load: the time inside the spans that paid for a program (every span that is
the ``parent`` of a ``jit_backend`` before the window: ``local_train``,
``stack``, ``pack``, ``eval``, ``api_init``, ...) less the ``jit_*`` intervals
inside them. Each span once, and a payer inside a payer (``store_upload``
in ``api_init``) not twice: the union of the payers' intervals less its
overlap with the union of the ``jit_*`` intervals. A ``jit_backend`` outside
any span (the benchmark's own ``_norms``) adds nothing."""

from benchmarks.lib import setup_spans as lib


def read(run):
    spans = lib.jit_setup_spans(run)
    if spans is None:
        return None
    paid = lib.payers(spans)
    jit = [(s, e) for n, s, e, _ in spans if n in lib.JIT]
    # |P| - |P and J| = |P or J| - |J|
    return lib.union_s(paid + jit) - lib.union_s(jit)
