"""95th percentile of due time to the start of the pump that served the
request (gateway and batcher wait)."""
from bench.readers import percentile, queue_waits_ms


def read(run):
    return percentile(queue_waits_ms(run), 95)
