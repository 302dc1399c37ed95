"""Device: `memory_stats()["peak_bytes_in_use"]` of the fullest chip, GB."""


def read(run):
    b = run.device["memory_peak_bytes"]
    return b / 1e9 if b else None
