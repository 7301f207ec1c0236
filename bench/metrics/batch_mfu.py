"""Model FLOPs of the useful work of the window's batches over their
pump wall time at the chip's bf16 peak, in %."""
from bench.readers import batch_mfu


def read(run):
    return batch_mfu(run)
