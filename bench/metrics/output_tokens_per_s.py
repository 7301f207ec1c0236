"""Output tokens completed per second by the batches started in the
window, over the window start to the end of the last of them."""
from bench.readers import output_tokens_per_s


def read(run):
    return output_tokens_per_s(run)
