"""Share of the program's ``prepare`` spans during which the device trace
shows an operation running: prepare time the device never waited for."""


def read(run):
    trace = run["trace"]
    spans = [(s, e) for n, s, e, _ in run["program_spans"] if n == "prepare"]
    if trace is None or not spans:
        return None
    total = hidden = 0.0
    for s, e in spans:
        lo, hi = run["to_trace_ns"](s), run["to_trace_ns"](e)
        total += hi - lo
        hidden += run["covered"](trace["merged"], lo, hi)
    return 100.0 * hidden / total if total > 0 else None
