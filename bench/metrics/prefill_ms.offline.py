"""Mean device time of one prefill program (module name holds
``prefill_step``)."""
from bench.readers import program_ms


def read(run):
    return program_ms(run, "prefill_step")
