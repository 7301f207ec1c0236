"""Mean host time per decode step from the host copy of the previous
token to the return of the next decode launch, less its libhas sleep:
what the device waits on for each token (``BatchRecord.turnaround_s``)."""
from bench.records import turnaround_ms


def read(run):
    return turnaround_ms(run)
