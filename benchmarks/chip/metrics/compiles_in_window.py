"""Engine: programs JAX built (compiled or read from the persistent
cache) inside the measured window; set-up should leave none."""


def read(run):
    return float(run.compiles_in_window)
