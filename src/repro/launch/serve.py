"""Serving launcher: deploy a function under HAS-GPU control and replay a
workload through the real engine, or lower the serving steps against the
production mesh (--dry-run).

The named config is served as published, on the default device.
``--reduced`` serves its small same-family variant instead, for a CPU
rehearsal with ``JAX_PLATFORMS=cpu``.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
      --requests 16 [--sm 4 --quota 0.5 --batch 4] [--reduced] [--dry-run]
"""
import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--sm", type=int, default=4)
    ap.add_argument("--quota", type=float, default=0.5)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the reduced config (CPU rehearsal)")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    if args.dry_run:
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=512 "
            + os.environ.get("XLA_FLAGS", ""))
        from repro.launch.dryrun import run_combo
        run_combo(args.arch, args.shape, multi_pod=args.multi_pod)
        return

    import jax
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.core.scheduler import HASGPUScheduler
    from repro.core.vgpu import PodAlloc, VirtualGPU
    from repro.launch import compile_cache
    from repro.serving import Gateway, InferenceRequest, PodEngine

    compile_cache.enable()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    print(f"[serve] {'reduced ' if args.reduced else ''}{cfg.name} on "
          f"{jax.devices()[0].device_kind}, pod sm={args.sm} "
          f"q={args.quota} batch={args.batch}")
    vgpu = VirtualGPU("GPU-0", window_ms=50.0)
    sched = HASGPUScheduler()
    gw = Gateway()
    pod = PodAlloc(fn_id=f"fn-{cfg.name}", sm=args.sm, quota=args.quota,
                   batch=args.batch)
    vgpu.place(pod)
    gw.register(pod.fn_id, PodEngine(cfg, pod, vgpu, sched, max_seq=64))

    rng = np.random.default_rng(0)
    t0 = time.monotonic()
    for _ in range(args.requests):
        gw.route(pod.fn_id, InferenceRequest(
            prompt=rng.integers(1, cfg.vocab_size, 8).astype(np.int32),
            max_new_tokens=args.new_tokens))
    done = []
    while len(done) < args.requests:
        done.extend(gw.pump(pod.fn_id))
    lats = sorted(r.latency for r in done)
    print(f"served {len(done)} requests in {time.monotonic()-t0:.2f}s  "
          f"p50={lats[len(lats)//2]*1e3:.0f}ms p95={lats[int(len(lats)*0.95)-1]*1e3:.0f}ms")


if __name__ == "__main__":
    main()
