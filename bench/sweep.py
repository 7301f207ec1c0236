"""Finds an open-loop cell's knee: the highest offered rate at which the
queue does not grow over the window.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1.2,1.6,2.0 [--steady]

One process, one set-up, then one window (and its drain) per rate, on the
same served path as ``bench/run.py``. Each rate prints one JSON line: the
requests due, those still queued when the window closed, the median and
95th-percentile latency, and the mean latency of the window's first and
second halves (a queue that grows shows as a second half slower than the
first). The knee is the highest rate at which, there and at every lower
rate, nothing is queued at the close, and at which the second half's
mean is within 25 % of the first's; the last line gives it. At lower
rates the halves are not compared: there a few long outputs, and which
half of the one schedule they fall in, set the means (on a v5e the
halves read 1.44 and 1.56 apart at 3 and 2 req/s with no queue left).
Four fifths of the knee go into a steady mix's file, once; the cell then
offers that fixed rate.

``--steady`` sweeps a bursty mix in its steady form: Poisson arrivals at
each offered rate, with the mix's lengths and pod. A bursty mix's base
rate is a share of that knee. Without it, a bursty mix's rate is its
base rate, bursts kept. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cell as cell_mod  # noqa: E402
from bench import readers  # noqa: E402
from bench import spec as spec_mod  # noqa: E402


def sweep(cell: spec_mod.Cell, seed: int, seconds: float, rates):
    """Yields one record per rate."""
    server = cell_mod.Server(cell, seed)
    server.warm(seed)
    for rate in rates:
        with cell_mod.CompileCounter() as counter:
            w = cell_mod.serve_window(server, seconds, seed, counter, False,
                                      rate_per_s=rate)
        reqs = w["attempted"]
        lat = [r.request.completed_at - r.due for r in reqs
               if r.request.completed_at is not None]
        half = len(lat) // 2
        yield {"rate_per_s": rate, "due": len(reqs),
               "queued_at_close": w["queued_at_close"],
               "failed": len(reqs) - len(lat),
               "batches": len(w["batches"]),
               "mean_batch": (sum(b.size for b in w["batches"])
                              / max(len(w["batches"]), 1)),
               "mean_batch_s": (sum(b.end - b.start for b in w["batches"])
                                / max(len(w["batches"]), 1)),
               "latency_p50_s": readers.percentile(lat, 50),
               "latency_p95_s": readers.percentile(lat, 95),
               "first_half_mean_s": sum(lat[:half]) / max(half, 1),
               "second_half_mean_s": (sum(lat[half:])
                                      / max(len(lat) - half, 1)),
               "compiles_in_window": counter.count}


def steady(cell: spec_mod.Cell) -> spec_mod.Cell:
    """``cell`` with Poisson arrivals in place of its mix's own."""
    return dataclasses.replace(cell, traffic=dict(
        cell.traffic, arrivals={"kind": "poisson"}))


def knee(records) -> float:
    """The highest rate at which, there and at every lower rate, nothing
    was queued at the close and no request failed, and at which the
    second half's mean latency was within 25 % of the first's; 0 if no
    rate passes."""
    best = 0.0
    for rec in sorted(records, key=lambda r: r["rate_per_s"]):
        if rec["queued_at_close"] or rec["failed"]:
            break
        if rec["second_half_mean_s"] <= 1.25 * rec["first_half_mean_s"]:
            best = rec["rate_per_s"]
    return best


def main(argv=None) -> int:
    from bench.run import prepare
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--steady", action="store_true")
    args = ap.parse_args(argv)
    cell = spec_mod.load_cell(args.workload)
    if args.steady:
        cell = steady(cell)
    problem = prepare(cell)
    if problem:
        print(f"sweep: {problem}", file=sys.stderr)
        return 1
    recs = []
    for rec in sweep(cell, args.seed, args.seconds,
                     [float(r) for r in args.rates.split(",")]):
        recs.append(rec)
        print(json.dumps(rec), flush=True)
    print(json.dumps({"workload": args.workload, "steady": args.steady,
                      "knee_per_s": knee(recs)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
