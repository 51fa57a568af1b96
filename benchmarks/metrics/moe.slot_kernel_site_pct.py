"""Share of the round program's sums over a token's slots (the forward of
``weighted_rows`` and the backward of the dispatch gather ``take_rows``, two
an expert layer and step) that take this repo's Pallas kernel
(``slot_sum``, which reads only the rows of live slots) in place of XLA's
``sum_readers`` (a row read at every slot) at the training batch, from the
``flush`` spans' ``moe_slot_kernel_sites`` and ``moe_slot_sites``: the
program's own decision (``ops/slot_sum.takes_kernel``), a host number
carried by every flush of a model with routed experts. ``None`` where no
flush span carries them (a model without routed experts, or a program from
before the kernel existed)."""


def read(run):
    flushes = [a for n, _, _, a in run["program_spans"] if n == "flush" and a.get("moe_slot_sites")]
    if not flushes:
        return None
    return 100.0 * sum(a["moe_slot_kernel_sites"] for a in flushes) / sum(
        a["moe_slot_sites"] for a in flushes)
