"""Peak of the live device buffers on the fullest chip after the window
(``memory_stats()["peak_bytes_in_use"]``), in GiB: parameters, the
population, cohort batches. The space the runtime reserves for the
programs' temporaries is booked apart: ``device.hbm_scratch_gib``."""


def read(run):
    peak = run["memory_stats"].get("peak_bytes_in_use", 0)
    return peak / 2**30 if peak else None
