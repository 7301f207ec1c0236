"""Backend compiles inside the measured window (should be 0)."""


def read(run):
    return run.compiles_in_window
