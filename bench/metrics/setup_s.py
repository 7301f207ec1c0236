"""Process start to the first request of the window: imports, device
start, weights, loading or compiling every program, warm-up."""


def read(run):
    return run.t_window - run.t_start
