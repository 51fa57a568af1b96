"""Device memory the runtime has reserved for the loaded programs'
temporaries (``memory_stats()["peak_bytes_reserved"]``), in GiB: a pool
apart from the live buffers, which only grows, and which buffers cannot
use. ``round.temp_gib`` says how much of it the round program asks for."""


def read(run):
    stats = run["memory_stats"]
    held = stats.get("peak_bytes_reserved", stats.get("bytes_reserved", 0))
    return held / 2**30 if held else None
