"""Device time of the round program (local training and aggregate, one
jitted program) per round, from the trace's XLA Modules line."""


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    t = sum(v for k, v in trace["programs"].items() if "round_fn" in k)
    return 1e3 * t / run["rounds"] if t > 0 else None
