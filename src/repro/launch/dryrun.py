"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
  PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun

Writes one JSON record per combo (memory analysis, cost analysis, HLO
analyzer roofline terms, collective schedule) consumed by
benchmarks/roofline.py and EXPERIMENTS.md.
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import ARCHS, SHAPES, combo_is_supported, get_config, get_shape  # noqa: E402
from repro.launch import hlo_analysis  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import build_case, lower_case  # noqa: E402

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12      # bf16
HBM_BW = 819e9           # bytes/s
ICI_BW = 50e9            # bytes/s per link


def roofline_terms(analysis, n_chips):
    """Per-device analysis -> the three roofline terms in seconds."""
    compute_s = analysis.flops / PEAK_FLOPS
    memory_s = analysis.hbm_bytes / HBM_BW
    collective_s = analysis.collective_bytes / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    terms["dominant"] = max(terms, key=lambda k: terms[k])
    return terms


def run_combo(arch: str, shape: str, multi_pod: bool, verbose=True):
    cfg = get_config(arch)
    shp = get_shape(shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.time()
    case = build_case(cfg, shp, mesh)
    lowered = lower_case(case, mesh)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    analysis = hlo_analysis.analyze(hlo_text, case.scan_trip_hints)
    terms = roofline_terms(analysis, n_chips)

    record = {
        "arch": arch, "shape": shape, "step": case.step_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": int(n_chips),
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
        "memory": {
            "argument_bytes_per_device": int(mem.argument_size_in_bytes),
            "output_bytes_per_device": int(mem.output_size_in_bytes),
            "temp_bytes_per_device": int(mem.temp_size_in_bytes),
            "peak_bytes_per_device": int(mem.argument_size_in_bytes
                                         + mem.temp_size_in_bytes),
        },
        "xla_cost_analysis": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "hlo_analysis_per_device": {
            "flops": analysis.flops,
            "hbm_bytes": analysis.hbm_bytes,
            "collective_bytes": analysis.collective_bytes,
            "collectives": analysis.collectives,
            "while_trips": analysis.while_trips,
            "unknown_trip_whiles": analysis.unknown_trip_whiles,
        },
        "roofline": terms,
    }
    if verbose:
        print(f"[{record['mesh']}] {arch} x {shape}: "
              f"lower {record['lower_s']}s compile {record['compile_s']}s | "
              f"peak/dev {record['memory']['peak_bytes_per_device']/2**30:.2f} GiB | "
              f"flops/dev {analysis.flops:.3e} coll/dev "
              f"{analysis.collective_bytes:.3e}B | dominant "
              f"{terms['dominant']} "
              f"({max(terms['compute_s'], terms['memory_s'], terms['collective_s']):.2e}s)",
              flush=True)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    combos = []
    archs = [args.arch] if args.arch else sorted(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    for a in archs:
        for s in shapes:
            if combo_is_supported(a, s):
                combos.append((a, s))
            else:
                print(f"SKIP {a} x {s} (see DESIGN.md §Arch-applicability)")

    meshes = []
    if not args.multi_pod_only:
        meshes.append(False)
    if not args.single_pod_only:
        meshes.append(True)

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for multi_pod in meshes:
        for a, s in combos:
            tag = f"{a}__{s}__{'2x16x16' if multi_pod else '16x16'}"
            path = os.path.join(args.out, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"skip existing {tag}")
                continue
            try:
                rec = run_combo(a, s, multi_pod)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
            except Exception as e:  # a failure here is a sharding bug
                failures.append((tag, repr(e)))
                print(f"FAIL {tag}: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nALL DRY-RUN COMBOS PASSED")


if __name__ == "__main__":
    main()
