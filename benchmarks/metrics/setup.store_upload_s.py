"""Seconds of the program's ``store_upload`` span (``FedAvgAPI.__init__``:
the shards concatenated as padded rows on the host, and the upload), before
the window. None where the program keeps no device store."""

from benchmarks.lib import setup_spans as lib


def read(run):
    uploads = [e - s for n, s, e, _ in lib.setup_spans(run) if n == "store_upload"]
    return sum(uploads) / 1e6 if uploads else None
