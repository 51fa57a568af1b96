"""Host time the train loop spends logging the rows it flushes, per round of
the window: the benchmark's span round ``_log_round`` less the program's
``eval`` spans inside it (they are host.eval_ms). The wait for the device
inside ``_flush_pending`` is not host work and is left out; where the device
idles meanwhile, the breakdown's idle gaps name ``bench.flush``."""


def read(run):
    logs = [e - s for n, s, e in run["bench_spans"] if n == "bench.log"]
    if not logs:
        return None
    evals = sum(e - s for n, s, e, _ in run["program_spans"] if n == "eval")
    return (sum(logs) - evals) / 1e3 / run["rounds"]
