"""Share of the round program's token-table gradients (one a local step:
the token lookup's, added onto the head's weight gradient where the table is
also the head) that take this repo's Pallas kernel (``table_grad``) in place
of XLA's scatter, at the training batch, from the ``flush`` spans'
``embed_kernel_sites`` and ``embed_sites``: the program's own decision
(``ops/table_grad.takes_kernel``), a host number carried by every flush of a
decoder. ``None`` where no flush span carries them (a model with no token
table of the decoder's, or a program from before the kernel existed)."""


def read(run):
    flushes = [a for n, _, _, a in run["program_spans"] if n == "flush" and a.get("embed_sites")]
    if not flushes:
        return None
    return 100.0 * sum(a["embed_kernel_sites"] for a in flushes) / sum(
        a["embed_sites"] for a in flushes)
