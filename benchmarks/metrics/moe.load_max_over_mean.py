"""Imbalance of the held experts' load: the largest number of pairs one held
expert got over the mean, each summed over the expert layers' calls in the
window (``moe_load_max``, ``moe_load_mean`` on the ``flush`` spans). What a
grouped product's tiles, and later an all-to-all, pay for."""


def read(run):
    flushes = [a for n, _, _, a in run["program_spans"] if n == "flush" and "moe_load_mean" in a]
    mean = sum(a["moe_load_mean"] for a in flushes)
    if not mean:
        return None
    return sum(a["moe_load_max"] for a in flushes) / mean
