"""Host time to select, stack and place a round's cohort batch, per round:
the program's ``broadcast`` span plus the benchmark's span round
``_pipeline_prepare`` (which prepares the next round while this one runs)."""


def read(run):
    place = sum(e - s for n, s, e, _ in run["program_spans"] if n == "broadcast")
    prepare = sum(e - s for n, s, e in run["bench_spans"] if n == "bench.prepare")
    if not place and not prepare:
        return None
    return (place + prepare) / 1e3 / run["rounds"]
