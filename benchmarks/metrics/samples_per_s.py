"""Real (unpadded) client samples trained in the window's rounds, as the
feed counts them, over the window's time."""


def read(run):
    if run["unit_name"] != "samples":
        return None
    return run["units"] / run["elapsed_s"]
