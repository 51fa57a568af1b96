"""Share of the round program's state-space scans (``ops/ssd.ssd``, one a
``M`` layer and local step) that take this repo's Pallas kernels
(``ssd_fwd``, ``ssd_bwd``) in place of the chunked products XLA lowers, at
the training length, from the ``flush`` spans' ``ssd_kernel_sites`` and
``ssd_sites``: the program's own decision (``ops/ssd.takes_kernel``), a host
number carried by every flush of a model with state-space layers. ``None``
where no flush span carries them (a model without such layers, or a program
from before the kernels existed)."""


def read(run):
    flushes = [a for n, _, _, a in run["program_spans"] if n == "flush" and a.get("ssd_sites")]
    if not flushes:
        return None
    return 100.0 * sum(a["ssd_kernel_sites"] for a in flushes) / sum(a["ssd_sites"] for a in flushes)
