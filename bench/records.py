"""The program's own record of each served batch, for the metric readers
that read it.

``PodEngine.step`` stamps every request of a batch with one shared
``BatchRecord`` (``InferenceRequest.batch_record``): when the batch left
the queue and when its outputs were stamped, the seconds its libhas
acquires slept, and the host time the device waited on for its tokens.
A program that keeps no such record gives None here, and the metric is
left out of the line.
"""
from __future__ import annotations

import math
from collections import Counter
from typing import List, Optional


def batch_records(run) -> Optional[list]:
    """The record of each batch started in the window, in order."""
    recs = [getattr(b.reqs[0].request, "batch_record", None)
            for b in run.batches]
    return recs if recs and None not in recs else None


def sleep_share(run) -> Optional[float]:
    """Seconds slept in the libhas acquires over the batches' own wall
    time, in %."""
    recs = batch_records(run)
    if recs is None:
        return None
    wall = sum(r.ended - r.started for r in recs)
    return 100.0 * sum(r.slept_s for r in recs) / wall if wall > 0 else None


def turnaround_ms(run) -> Optional[float]:
    """Host time the device waited on per decode step."""
    recs = batch_records(run)
    steps = sum(r.steps for r in recs) if recs else 0
    if not steps:
        return None
    return 1e3 * sum(r.turnaround_s for r in recs) / steps


def batch_fill(run) -> Optional[float]:
    """Mean rows per batch started in the window over the pod's batch,
    in %; a batch's rows are the attempted requests stamped with its
    record."""
    recs = batch_records(run)
    if recs is None:
        return None
    rows = Counter(id(r.request.batch_record) for r in run.attempted)
    return (100.0 * sum(rows[id(rec)] for rec in recs)
            / (len(recs) * run.cell.traffic["pod"]["batch"]))


def admit_waits_ms(run) -> Optional[List[float]]:
    """Arrival to the start of the batch that served it, for every
    attempted request; inf for one not served."""
    if not any(hasattr(r.request, "batch_record") for r in run.attempted):
        return None
    return [(r.request.batch_record.started - r.request.arrival) * 1e3
            if r.request.batch_record is not None else math.inf
            for r in run.attempted]
