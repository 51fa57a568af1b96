"""Device time of the device store's gather program (``device_store_gather``:
gather, zero the padding, reshape to clients x steps x batch) per round,
from the trace's XLA Modules line."""


def read(run):
    trace = run["trace"]
    if trace is None:
        return None
    t = sum(v for k, v in trace["programs"].items() if "device_store_gather" in k)
    return 1e3 * t / run["rounds"] if t > 0 else None
