"""Seconds the libhas token acquires slept over the wall time of the
window's batches, in %: the share of each batch the charge, and not the
chip, decides (``BatchRecord.slept_s``, from ``GPUClient.acquire``)."""
from bench.records import sleep_share


def read(run):
    return sleep_share(run)
