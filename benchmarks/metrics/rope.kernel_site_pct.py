"""Share of the round program's calls of the rotate-half rotary operator that
take its one-pass kernel at the training length, from the ``flush`` spans'
``rope_kernel_sites`` and ``rope_sites``: the program's own decision
(``ops/rotary.takes_kernel``), a host number carried by every flush of a model
whose layers turn q and k that way. ``None`` where no flush span carries them
(a model without such a call, or a program from before the operator existed)."""


def read(run):
    flushes = [a for n, _, _, a in run["program_spans"] if n == "flush" and a.get("rope_sites")]
    if not flushes:
        return None
    return 100.0 * sum(a["rope_kernel_sites"] for a in flushes) / sum(a["rope_sites"] for a in flushes)
