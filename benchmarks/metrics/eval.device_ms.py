"""Device time of one evaluation: the ``eval_fn`` program on the trace's XLA
Modules line over the window's evaluations (the program's ``eval`` spans)."""


def read(run):
    trace = run["trace"]
    evals = sum(1 for n, *_ in run["program_spans"] if n == "eval")
    if trace is None or not evals:
        return None
    t = sum(v for k, v in trace["programs"].items() if "eval_fn" in k)
    return 1e3 * t / evals if t > 0 else None
