"""Real samples over the sample slots of the cohort batches the program
placed in the window (clients x bucketed steps x batch): what the program's
bucketing and padding to the cohort's largest client cost. Both numbers are
the program's: the shape of the mask of each batch it handed to
``_place_batch`` and that batch's ``num_samples``."""


def read(run):
    if not run["placed"] or run["unit_name"] != "samples":
        return None
    slots, real = run["placed"]
    return 100.0 * real / slots if slots else None
