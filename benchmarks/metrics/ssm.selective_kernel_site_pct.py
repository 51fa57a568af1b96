"""Share of the round program's selective scans (``ops/selective_scan``, one a
Mamba-1 layer and local step) that take this repo's Pallas kernels
(``sscan_fwd``, ``sscan_bwd``) in place of the plain pass over positions, at
the training length, from the ``flush`` spans' ``sscan_kernel_sites`` and
``sscan_sites``: the program's own decision
(``ops/selective_scan.takes_kernel``), a host number carried by every flush of
a model with Mamba-1 layers. ``None`` where no flush span carries them (a
model without such layers, or a program from before the kernels existed)."""


def read(run):
    flushes = [a for n, _, _, a in run["program_spans"] if n == "flush" and a.get("sscan_sites")]
    if not flushes:
        return None
    return 100.0 * sum(a["sscan_kernel_sites"] for a in flushes) / sum(
        a["sscan_sites"] for a in flushes)
