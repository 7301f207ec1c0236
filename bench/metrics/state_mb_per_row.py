"""Megabytes (1e6 bytes) of recurrent state a row of a batch holds in
its cache: the program's ``BatchRecord.state_bytes`` summed over the
window's batches, over their rows. None where the program keeps no such
count."""
from bench.records import batch_records


def read(run):
    recs = batch_records(run)
    if recs is None or not all(hasattr(r, "state_bytes") for r in recs):
        return None
    rows = sum(b.size for b in run.batches)
    return sum(r.state_bytes for r in recs) / rows / 1e6
