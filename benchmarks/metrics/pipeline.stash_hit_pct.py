"""Share of the window's rounds whose ``broadcast`` found its batch already
prepared (the span's ``prepared`` attribute: the batch came from the
pipeline's stash). The window's first round is never prepared: the
``train()`` call before it ended at its own horizon."""


def read(run):
    said = [a["prepared"] for n, _, _, a in run["program_spans"]
            if n == "broadcast" and "prepared" in a]
    return 100.0 * sum(1 for p in said if p) / len(said) if said else None
