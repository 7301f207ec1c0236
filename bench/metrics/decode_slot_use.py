"""Useful output tokens over rows x decode steps of the window's
batches, in %: what static batching wastes on rows already done."""
from bench.readers import slot_use


def read(run):
    return slot_use(run)
