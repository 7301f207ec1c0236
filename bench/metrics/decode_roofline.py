"""Least time of the window's decode steps (needed bytes at the HBM
peak or FLOPs at the bf16 peak, whichever is larger) over their device
time, in %."""
from bench.readers import decode_roofline


def read(run):
    return decode_roofline(run)
