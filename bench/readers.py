"""Arithmetic shared by the metric readers in ``bench/metrics/``.

Each reader is a file of its own that calls one of these on a ``Run``
(``bench/cell.py``) and returns a number, or None where the run holds
nothing to read: then the harness leaves the metric out of the line.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence

from bench import trace as tr


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile; an infinite value (a failed
    request) counts above every finite one."""
    v = sorted(values)
    if not v:
        return None
    x = q / 100.0 * (len(v) - 1)
    lo, hi = math.floor(x), math.ceil(x)
    if math.isinf(v[hi]):
        return math.inf
    return v[lo] + (v[hi] - v[lo]) * (x - lo)


def latencies(run) -> List[float]:
    """Due time to completion of every attempted request; inf if none."""
    return [r.request.completed_at - r.due
            if r.request.completed_at is not None else math.inf
            for r in run.attempted]


def queue_waits_ms(run) -> List[float]:
    """Due time to the start of the pump that served it."""
    return [(r.pump_start - r.due) * 1e3 if r.pump_start is not None
            else math.inf for r in run.attempted]


def output_tokens_per_s(run) -> Optional[float]:
    """Tokens of the completed requests of the batches started in the
    window, over the window's start to the end of the last of them."""
    toks = sum(r.planned.max_new for b in run.batches for r in b.reqs
               if r.request.completed_at is not None)
    span = run.t_last_end - run.t_window
    return toks / span if toks and span > 0 else None


def slot_use(run) -> Optional[float]:
    """Useful output tokens over rows x decode steps, in %."""
    slots = sum(b.size * b.n_new for b in run.batches)
    useful = sum(r.planned.max_new for b in run.batches for r in b.reqs)
    return 100.0 * useful / slots if slots else None


def step_programs(run, key: str) -> list:
    """The traced batches' programs whose name holds ``key``, in order:
    one ``prefill_step`` a batch, one ``decode_step`` a served token. The
    drain's first batch starts right after the window closes and can
    start inside it on the device's clock, so programs past that count
    are dropped."""
    tb = run.traced_batches
    want = (len(tb) if key == "prefill_step"
            else sum(b.n_new for b in tb))
    return tr.programs(run.trace, key)[:want]


def program_ms(run, key: str) -> Optional[float]:
    """Mean device time of one of the traced batches' programs whose
    name holds ``key``."""
    ps = step_programs(run, key)
    if not ps:
        return None
    return sum(e[2] - e[1] for e in ps) / len(ps) / 1e6


def decode_host_gap_ms(run) -> Optional[float]:
    """Mean time from the end of one decode program to the start of the
    next within one pump."""
    decodes = step_programs(run, "decode_step")
    gaps = [g for grp in tr.grouped_by_span(run.trace, decodes, "pump")
            for g in tr.gaps_between(grp)]
    return sum(gaps) / len(gaps) / 1e6 if gaps else None


def _least_s(run, flops: float, nbytes: float) -> float:
    p = run.peaks
    return max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])


def decode_roofline(run) -> Optional[float]:
    """The least time the decode steps could take over their measured
    device time, in %. The traced decode programs are matched to the
    steps of the traced batches in order; None where the trace holds
    fewer (a program lost)."""
    fam, s = run.cell.family, run.shape
    want = [fam.decode_cost(s, b.size, b.prompt_len + i)
            for b in run.traced_batches for i in range(b.n_new)]
    got = step_programs(run, "decode_step")
    if not got or len(got) != len(want):
        return None
    least = sum(_least_s(run, f, n) for f, n in want)
    spent = sum(e[2] - e[1] for e in got) / 1e9
    return 100.0 * least / spent


def batch_mfu(run) -> Optional[float]:
    """Model FLOPs of the useful work of the window's batches over their
    pump wall time at the chip's peak, in %."""
    fam, s = run.cell.family, run.shape
    flops = sum(fam.request_flops(s, b.prompt_len, r.planned.max_new)
                for b in run.batches for r in b.reqs)
    wall = sum(b.end - b.start for b in run.batches)
    if not wall:
        return None
    return 100.0 * flops / (wall * run.peaks["bf16_flops_per_s"])


def idle_share(run) -> Optional[float]:
    lo, hi = run.trace.window()
    busy = tr.busy_ns(run.trace)
    if hi <= lo or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / (hi - lo))
