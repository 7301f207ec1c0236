"""Least time of the traced batches' prefills (the family's
``prefill_cost``: FLOPs at the bf16 peak or needed bytes at the HBM
peak, whichever is larger) over their measured ``jit_prefill_step``
device time, in %. The traced prefill programs are matched to the traced
batches in order; None where the trace holds fewer (a program lost)."""
from bench.readers import _least_s, step_programs


def read(run):
    fam = run.cell.family
    want = [fam.prefill_cost(run.shape, b.size, b.prompt_len)
            for b in run.traced_batches]
    got = step_programs(run, "prefill_step")
    if not got or len(got) != len(want):
        return None
    least = sum(_least_s(run, f, n) for f, n in want)
    return 100.0 * least / (sum(e[2] - e[1] for e in got) / 1e9)
