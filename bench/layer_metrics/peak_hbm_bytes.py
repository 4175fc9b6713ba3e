"""Device: `memory_stats()["peak_bytes_in_use"]` of the fullest chip,
read after the window."""


def read(run: dict):
    return run["memory_peak_bytes"]
