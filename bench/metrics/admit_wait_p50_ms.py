"""Median of arrival to the start of the batch that served the request,
as the program stamped it (``BatchRecord.started``); a request not
served counts as infinite."""
from bench.readers import percentile
from bench.records import admit_waits_ms


def read(run):
    waits = admit_waits_ms(run)
    return percentile(waits, 50) if waits else None
