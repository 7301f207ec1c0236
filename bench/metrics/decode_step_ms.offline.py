"""Mean device time of one decode program (module name holds
``decode_step``)."""
from bench.readers import program_ms


def read(run):
    return program_ms(run, "decode_step")
