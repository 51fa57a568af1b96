"""Share of the round program's attention call sites that take the blockwise
kernel (no T x T scores in HBM) at the training length, from the ``flush``
spans' ``attn_kernel_sites`` and ``attn_sites``: the program's own decision
(``ops/attention.takes_kernel``), a host number carried by every flush of a
model that has attention. ``None`` where no flush span carries them (a model
without attention, or a program from before the decision existed)."""


def read(run):
    flushes = [a for n, _, _, a in run["program_spans"] if n == "flush" and a.get("attn_sites")]
    if not flushes:
        return None
    return 100.0 * sum(a["attn_kernel_sites"] for a in flushes) / sum(a["attn_sites"] for a in flushes)
