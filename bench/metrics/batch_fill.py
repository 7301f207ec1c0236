"""Mean rows per batch over the pod's batch, over the window's batches,
in %: rows are the requests that the program stamped with each batch's
``BatchRecord``. Static batches that leave before the queue fills read
low; continuous batching would raise it."""
from bench.records import batch_fill


def read(run):
    return batch_fill(run)
