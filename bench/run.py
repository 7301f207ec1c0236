"""Chip benchmark of the served path: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose JAX finds a TPU with as
many chips as the cell asks for; anywhere else it exits 1 and prints no
result. ``<cell>`` is a ``workloads`` name in ``BENCHMARK.json``; its
configuration, traffic mix, check and metric readers are files under
``bench/`` found by name (``bench/spec.py``). The compile cache is the
program's (``repro.launch.compile_cache``): ``JAX_COMPILATION_CACHE_DIR``
if set, else ``<checkout>/.jax_cache``.

With ``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window, and a ``breakdown``. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
(``breakdown``), and last ``checks``, each number compared beside its
limit, which also end standard error.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cell as cell_mod  # noqa: E402
from bench import spec as spec_mod  # noqa: E402
from bench import trace as trace_mod  # noqa: E402


def parse(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def peaks_for(kind: str) -> dict:
    """This device's row of ``bench/peaks.json``; a device missing from
    the table is an error."""
    with open(spec_mod.BENCH / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


def prepare(cell: spec_mod.Cell, require_chip: bool = True) -> Optional[str]:
    """What keeps this machine from running ``cell`` (no TPU, too few
    chips), else None after pointing JAX at the program's compile cache;
    every program, small ones too, is kept there."""
    import jax
    from repro.launch import compile_cache
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        return f"needs a TPU; JAX found {devices[0].platform!r}"
    if require_chip and len(devices) < cell.chips:
        return f"needs {cell.chips} chips; JAX found {len(devices)}"
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return None


def checks_of(gap: float, bad_outputs: int, tokens: int,
              limit: float) -> dict:
    """The numbers the output check compares, each beside its limit."""
    return {"logit_gap": {"value": gap, "limit": limit},
            "bad_outputs": {"value": bad_outputs, "limit": 0},
            "checked_tokens": {"value": tokens, "limit": 1}}


def check(server: cell_mod.Server, attempted, seed: int) -> dict:
    """The output check: every completed output well-formed, and the
    widest logit gap of a seeded sample (the longest request in it)
    within the cell's limit."""
    c = server.cell.check
    sample = cell_mod.check_sample(attempted, c["check_requests"], seed)
    gaps = cell_mod.logit_gaps(server.cell.family, server.shape,
                               server.weights, sample,
                               server.pod_spec["max_seq"])
    return checks_of(gaps["served_gap"],
                     cell_mod.output_faults(attempted, server.shape.vocab),
                     gaps["tokens"], c["logit_gap_limit"])


def passes(checks: dict) -> bool:
    return (checks["logit_gap"]["value"] <= checks["logit_gap"]["limit"]
            and checks["bad_outputs"]["value"] <= 0
            and checks["checked_tokens"]["value"] >= 1)


def run(args: argparse.Namespace, cell: Optional[spec_mod.Cell] = None,
        require_chip: bool = True, t_start: float = T_START) -> Optional[dict]:
    """One run; None (after a message on stderr) where the machine
    cannot run the cell. ``cell`` defaults to ``args.workload``'s."""
    import jax

    cell = cell or spec_mod.load_cell(args.workload)
    problem = prepare(cell, require_chip)
    if problem:
        print(f"bench: {problem}", file=sys.stderr)
        return None
    devices = jax.devices()
    dev = devices[0]
    peaks = peaks_for(dev.device_kind) if require_chip else {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    server = cell_mod.Server(cell, args.seed)
    server.warm(args.seed)
    traced = bool(args.trace)
    with cell_mod.CompileCounter() as counter:
        w = cell_mod.serve_window(server, args.seconds, args.seed, counter,
                                  traced)
    stats = dev.memory_stats() or {}
    run_ = cell_mod.Run(
        cell=cell, shape=server.shape, peaks=peaks,
        t_start=t_start, t_window=w["t_window"], t_last_end=w["t_last_end"],
        attempted=w["attempted"], batches=w["batches"],
        compiles_in_window=counter.count,
        memory_peak_bytes=stats.get("peak_bytes_in_use"),
        trace=cell_mod.read_trace(w["log_dir"]) if traced else None,
        t_trace=w["t_trace"])
    if traced:
        tb = run_.traced_batches
        print(f"bench: traced from {w['t_trace'] - w['t_window']:.2f} s "
              f"of the window, {len(tb)} batches; programs in the trace: "
              f"{len(trace_mod.programs(run_.trace, 'prefill_step'))} "
              f"prefill (want {len(tb)}), "
              f"{len(trace_mod.programs(run_.trace, 'decode_step'))} decode "
              f"(want {sum(b.n_new for b in tb)})", file=sys.stderr)
    checks = check(server, run_.attempted, args.seed)

    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in cell.metrics_of(kind):
        v = m.reader.read(run_)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run_.memory_peak_bytes}
    out = {"correct": passes(checks), "attempted": len(run_.attempted),
           "failed": len(run_.failed), "metrics": metrics, "device": device}
    if traced:
        lo, hi = run_.trace.window()
        device["busy_s"] = trace_mod.busy_ns(run_.trace) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = {
            "device_ops": [list(x) for x in trace_mod.top_ops(run_.trace)],
            "idle_gaps": [list(x) for x in trace_mod.idle_gaps(run_.trace)]}
    out["checks"] = checks
    return out


def main(argv: Optional[List[str]] = None) -> int:
    out = run(parse(argv))
    if out is None:
        return 1
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
