"""Host time of the program's ``stack`` spans per round: building the
cohort's index and mask matrices on the host and dispatching the device
store's gather (or stacking the batch on the host where there is no store)."""


def read(run):
    stacks = [e - s for n, s, e, _ in run["program_spans"] if n == "stack"]
    return sum(stacks) / 1e3 / run["rounds"] if stacks else None
