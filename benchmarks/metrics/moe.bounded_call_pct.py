"""Share of the expert layers' calls whose held pairs stayed under the
layer's static row bound, so that one pass over the bounded rows was the
whole call (``1 - moe_overflow / moe_calls``, from the ``flush`` spans in the
window): 100 means no step paid for a second pass. ``None`` where no flush
span carries the two counters (a model without routed experts, or a program
from before the bound existed)."""


def read(run):
    flushes = [a for n, _, _, a in run["program_spans"] if n == "flush" and a.get("moe_calls")]
    if not flushes:
        return None
    return 100.0 * (1.0 - sum(a["moe_overflow"] for a in flushes) / sum(a["moe_calls"] for a in flushes))
