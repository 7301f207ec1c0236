"""Measured-profile calibration of the physics against the real stack.

Drives the actual jitted prefill/decode dispatch path of the serving
engine (``repro.serving.PodEngine`` behind the libhas token handshake)
across a deterministic (arch, GPU type, batch, sm, quota) grid and
writes a versioned calibration table (schema ``profile_stack/v1``) with
per-point measured seconds, the analytic roofline prediction for the
same dispatch, and pinned sim-vs-measured relative-error percentiles.
See ``src/repro/profiling/`` for the harness and the consumers
(``CapacityTable(calibration=...)``, the RaPP dataset builder) and
``docs/architecture.md`` ("Calibrating the physics") for the flow.

Usage::

    python -m benchmarks.profile_stack                  # default grid
    python -m benchmarks.profile_stack --smoke          # tiny CI grid
    python -m benchmarks.profile_stack --smoke --check benchmarks/ref_profile_cpu.json
    python -m benchmarks.profile_stack --smoke --update-ref
    python -m benchmarks.profile_stack --kernels        # + Pallas-vs-ref

On CPU the measured numbers validate the plumbing (grid, schema,
determinism — the roofline models an accelerator, so absolute error is
large and expected); on a real accelerator the same command calibrates
the physics. ``--check`` gates schema/grid/analytic drift exactly and
measured-shape drift by a generous machine-normalized factor, mirroring
``bench_control_plane``.
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.launch import compile_cache
from repro.profiling import (GridSpec, check_report, profile_kernels,
                             run_profile)

REF_PATH = "benchmarks/ref_profile_cpu.json"

SMOKE_GRID = GridSpec(
    archs=("olmo-1b", "mamba2-2.7b"),
    gpu_types=("v5e",),
    batches=(1, 2),
    sms=(2, 4),
    quotas=(0.5, 1.0),
    seq=32, window_ms=20.0, warmup=1, iters=3, reduce=True)

FULL_GRID = GridSpec(
    archs=("olmo-1b", "qwen2.5-3b", "mamba2-2.7b", "deepseek-moe-16b"),
    gpu_types=("v5e", "t4"),
    batches=(1, 2, 4, 8),
    sms=(1, 2, 4, 8),
    quotas=(0.3, 0.5, 1.0),
    seq=64, window_ms=20.0, warmup=2, iters=5, reduce=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny grid for CI (matches the committed "
                         "reference table)")
    ap.add_argument("--archs", nargs="+", help="override grid archs")
    ap.add_argument("--gpu-types", nargs="+",
                    help="override grid device types")
    ap.add_argument("--batches", nargs="+", type=int)
    ap.add_argument("--sms", nargs="+", type=int)
    ap.add_argument("--quotas", nargs="+", type=float)
    ap.add_argument("--seq", type=int, help="KV-cache budget per point")
    ap.add_argument("--warmup", type=int)
    ap.add_argument("--iters", type=int)
    ap.add_argument("--full-configs", action="store_true",
                    help="profile the full (non-reduced) architectures "
                         "(accelerator-sized; not for CPU)")
    ap.add_argument("--kernels", action="store_true",
                    help="also time each Pallas kernel vs its "
                         "kernels/ref.py oracle")
    ap.add_argument("--out", default="PROFILE_stack.json")
    ap.add_argument("--check", metavar="REF",
                    help="fail on schema/grid/analytic drift or "
                         "measured-shape drift vs this reference table")
    ap.add_argument("--factor", type=float, default=10.0,
                    help="max tolerated machine-normalized measured "
                         "drift (generous: absolute machine speed is "
                         "already cancelled)")
    ap.add_argument("--update-ref", action="store_true",
                    help=f"also write the report to {REF_PATH}")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)

    grid = SMOKE_GRID if args.smoke else FULL_GRID
    overrides = {}
    for field, cast in (("archs", tuple), ("gpu_types", tuple),
                        ("batches", tuple), ("sms", tuple),
                        ("quotas", tuple), ("seq", int),
                        ("warmup", int), ("iters", int)):
        v = getattr(args, field)
        if v is not None:
            overrides[field] = cast(v)
    if args.full_configs:
        overrides["reduce"] = False
    if overrides:
        import dataclasses
        grid = dataclasses.replace(grid, **overrides)

    compile_cache.enable()
    report = run_profile(grid, smoke=args.smoke, verbose=args.verbose)
    if args.kernels:
        report["kernels"] = profile_kernels(warmup=grid.warmup,
                                            iters=grid.iters)
        for k in report["kernels"]:
            print(f"kernel {k['name']:<18} {k['measured_s']*1e3:9.3f} ms"
                  f"  (ref {k['ref_s']*1e3:9.3f} ms, "
                  f"{k['ratio']:6.2f}x)")
    err = report["error"]
    print(f"{len(report['points'])} points on "
          f"{report['meta']['backend']} "
          f"({report['meta']['device_kind']})")
    for arch, e in sorted(err["per_arch"].items()):
        print(f"  {arch:<18} rel err p50 {e['p50']:10.2f}  "
              f"p95 {e['p95']:10.2f}  ({e['n']} points)")
    print(f"  {'overall':<18} rel err p50 {err['overall']['p50']:10.2f}  "
          f"p95 {err['overall']['p95']:10.2f}")
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")
    if args.update_ref:
        with open(REF_PATH, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(f"wrote {REF_PATH}")
    if args.check:
        with open(args.check) as f:
            ref = json.load(f)
        failures = check_report(report, ref, factor=args.factor)
        for msg in failures:
            print(f"FAIL  {msg}", file=sys.stderr)
        if failures:
            print(f"calibration check failed vs {args.check} "
                  f"({len(failures)} failure(s))", file=sys.stderr)
            return 1
        print(f"calibration check ok vs {args.check}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
