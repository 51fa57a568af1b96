"""Process start to the first measured round: import, population,
placement, the followed rounds, warm-up (and compilation in a first run)."""


def read(run):
    return run["setup_s"]
