"""Device: peak bytes in use on the chip by the end of the window
(memory_stats()["peak_bytes_in_use"]), in GB."""


def read(run):
    return None if run.memory_peak_bytes is None else run.memory_peak_bytes / 1e9
