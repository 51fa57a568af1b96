"""Rounds of the window's one ``train()`` call over the time to the
device-synchronised end of its last round."""


def read(run):
    return run["rounds"] / run["elapsed_s"]
