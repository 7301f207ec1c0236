"""Share of the traced window with no operation on the device, in %."""
from bench.readers import idle_share


def read(run):
    return idle_share(run)
