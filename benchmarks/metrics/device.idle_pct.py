"""Share of the traced window in which no operation ran on the device:
1 - union of the device's op intervals over the window."""


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
