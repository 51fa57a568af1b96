"""Rows of the grouped products that carry no pair of a held expert, as a
share of the rows they run over (``moe_rows`` less ``moe_pairs`` over
``moe_rows``, from the ``flush`` spans in the window): the static bound of
tokens x top-k rows against the pairs routing really sends here."""


def read(run):
    flushes = [a for n, _, _, a in run["program_spans"] if n == "flush" and "moe_rows" in a]
    rows = sum(a["moe_rows"] for a in flushes)
    if not rows:
        return None
    return 100.0 * (1.0 - sum(a["moe_pairs"] for a in flushes) / rows)
