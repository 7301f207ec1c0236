"""Mean time from the end of one decode program to the start of the
next within one pump: the host's share of each output token."""
from bench.readers import decode_host_gap_ms


def read(run):
    return decode_host_gap_ms(run)
