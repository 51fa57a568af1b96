"""Bytes of temporaries the compiler planned for the round program (the
largest ``temp_size_in_bytes`` among the loaded programs named ``round_fn``,
one per shape class and padding variant), in GiB: the activations and
per-client copies that a schedule or remat change moves."""


def read(run):
    if not run["programs"]:
        return None
    temps = [temp for name, temp, *_ in run["programs"] if "round_fn" in name]
    return max(temps) / 2**30 if temps and max(temps) > 0 else None
