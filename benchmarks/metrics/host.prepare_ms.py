"""Host time to select, stack and place a round's cohort batch, per round,
from the program's own spans: ``prepare`` (the pipeline building the next
round's batch while this one runs) plus ``broadcast`` (the round taking the
stash, or building the batch itself where nothing was prepared)."""


def read(run):
    spans = run["program_spans"]
    if not any(n == "broadcast" and "prepared" in a for n, _, _, a in spans):
        return None  # a program whose broadcast does not say where its batch came from
    total = sum(e - s for n, s, e, _ in spans if n in ("prepare", "broadcast"))
    return total / 1e3 / run["rounds"]
