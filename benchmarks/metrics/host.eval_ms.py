"""Host time of one evaluation (it waits for the device): the program's
``eval`` span, mean over the window's evaluation rounds."""


def read(run):
    evals = [e - s for n, s, e, _ in run["program_spans"] if n == "eval"]
    return sum(evals) / 1e3 / len(evals) if evals else None
