"""Readings that a cell's output-check limit is set from.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed, in one process: the cell's served path is built with that
seed's weights and serves a window at the cell's own load, exactly as in
``bench/run.py``; then, over the same seeded sample of finished requests,
the float32 reference gives two readings:

- ``served_gap``: the widest gap between the reference's best logit and
  its logit of a token the program served (the number the run checks);
- ``control_gap``: the same gap for the token that the control, the
  reference with its weights rounded to float8, puts first.

Each reading goes through the run's own output check (``run.passes``):
``correct`` for the program, ``control_correct`` with the control in the
program's place, which has to come out false on every seed. The largest
``served_gap`` over a dozen seeds or more is the limit's lower reading,
the smallest ``control_gap`` its upper one. Prints one JSON line per seed
and a summary line. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cell as cell_mod  # noqa: E402
from bench import spec as spec_mod  # noqa: E402


def readings(cell: spec_mod.Cell, seed: int, seconds: float) -> dict:
    """One seed's program and control readings on the cell's window."""
    from bench.run import checks_of, passes
    server = cell_mod.Server(cell, seed)
    server.warm(seed)
    with cell_mod.CompileCounter() as counter:
        w = cell_mod.serve_window(server, seconds, seed, counter, False)
    sample = cell_mod.check_sample(w["attempted"],
                                   cell.check["check_requests"], seed)
    gaps = cell_mod.logit_gaps(cell.family, server.shape, server.weights,
                               sample, server.pod_spec["max_seq"],
                               control=True)
    limit = cell.check["logit_gap_limit"]
    bad = cell_mod.output_faults(w["attempted"], server.shape.vocab)
    return {"seed": seed, "attempted": len(w["attempted"]),
            "sizes": sorted({b.size for b in w["batches"]}), **gaps,
            "correct": passes(checks_of(gaps["served_gap"], bad,
                                        gaps["tokens"], limit)),
            "control_correct": passes(checks_of(gaps["control_gap"], 0,
                                                gaps["tokens"], limit))}


def main(argv=None) -> int:
    from bench.run import prepare
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = spec_mod.load_cell(args.workload)
    problem = prepare(cell)
    if problem:
        print(f"control: {problem}", file=sys.stderr)
        return 1
    recs = []
    for seed in (int(s) for s in args.seeds.split(",")):
        recs.append(readings(cell, seed, args.seconds))
        print(json.dumps(recs[-1]), flush=True)
    print(json.dumps({
        "workload": cell.name, "seeds": len(recs),
        "lower_reading": max(r["served_gap"] for r in recs),
        "upper_reading": min(r["control_gap"] for r in recs),
        "limit_now": cell.check["logit_gap_limit"],
        "program_correct_every_seed": all(r["correct"] for r in recs),
        "control_failed_every_seed": not any(r["control_correct"]
                                             for r in recs)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
