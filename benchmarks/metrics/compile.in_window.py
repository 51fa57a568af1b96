"""XLA backend-compile events inside the measured window (persistent-cache
retrievals count). Expected 0: a window that compiled measured the
compiler."""


def read(run):
    return float(run["compiles_in_window"])
