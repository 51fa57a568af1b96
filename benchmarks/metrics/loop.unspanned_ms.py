"""Host time of the window that no span of the program accounts for, per
round: the window less its depth-0 spans (``round``, ``pack``, ``prepare``,
``health``, ``flush``, one after another in ``train()``). What is left is
the loop's own overhead between them: the chunk planning, the bookkeeping,
the benchmark's wrappers."""


def read(run):
    top = [(n, e - s) for n, s, e, a in run["program_spans"] if a.get("depth") == 0]
    if not any(n == "flush" for n, _ in top):
        return None  # a program that does not span its loop
    return (run["elapsed_s"] * 1e6 - sum(d for _, d in top)) / 1e3 / run["rounds"]
