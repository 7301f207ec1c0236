"""End-to-end driver: REAL model serving with batched requests under
HAS-GPU resource control, plus the full simulated platform comparison.

Part 1 serves a reduced qwen2.5 (2 layers, small widths, so it runs on
any device) through the Gateway -> PodEngine -> libhas token handshake,
demonstrating vertical scaling speeding up a live pod. ``chip_smoke.py``
serves the published widths on a TPU. Part 2 replays an Azure-style trace
through the cluster simulator for HAS vs KServe-like vs FaST-GShare-like.

Run:  PYTHONPATH=src python examples/serve_autoscale.py
"""
import time

import jax
import numpy as np

from repro.configs import ARCHS, reduced
from repro.core import (ClusterSimulator, FaSTGShareLikePolicy, FnSpec,
                        HybridAutoScaler, KServeLikePolicy, Reconfigurator,
                        SimConfig)
from repro.core.scheduler import HASGPUScheduler
from repro.core.vgpu import PodAlloc, VirtualGPU
from repro.serving import Gateway, InferenceRequest, PodEngine
from repro.workloads import standard_workload

# ---------------------------------------------------------------- part 1
print(f"=== live serving (reduced qwen2.5 on {jax.devices()[0].device_kind})"
      " ===")
cfg = reduced(ARCHS["qwen2.5-3b"])
vgpu = VirtualGPU("GPU-demo", window_ms=50.0)
sched = HASGPUScheduler()
gw = Gateway()
pod = PodAlloc(fn_id="fn-qwen", sm=4, quota=0.3, batch=4)
vgpu.place(pod)
engine = PodEngine(cfg, pod, vgpu, sched, max_seq=64)
gw.register("fn-qwen", engine)

rng = np.random.default_rng(0)


def serve_n(n):
    t0 = time.monotonic()
    for _ in range(n):
        gw.route("fn-qwen", InferenceRequest(
            prompt=rng.integers(1, cfg.vocab_size, 8).astype(np.int32),
            max_new_tokens=4))
    done = []
    while len(done) < n:
        done.extend(gw.pump("fn-qwen"))
    return (time.monotonic() - t0) / n


lat_low = serve_n(8)
engine.set_quota(vgpu, 0.9)  # vertical scale-up: same pod, more tokens
lat_high = serve_n(8)
print(f"per-request wall time at q=0.3: {lat_low*1e3:.0f} ms, "
      f"after vertical scale-up to q=0.9: {lat_high*1e3:.0f} ms "
      f"({lat_low/max(lat_high,1e-9):.2f}x faster, no restart)")

# ---------------------------------------------------------------- part 2
print("\n=== platform comparison on an Azure-style trace ===")
spec = FnSpec(ARCHS["qwen2.5-3b"])
arr = standard_workload(duration_s=120.0, base_rps=25.0, seed=11)
print(f"trace: {len(arr)} requests / 120 s")
for name, Policy, whole in [("HAS-GPU", HybridAutoScaler, False),
                            ("KServe-like", KServeLikePolicy, True),
                            ("FaST-GShare-like", FaSTGShareLikePolicy, False)]:
    recon = Reconfigurator(num_gpus=0, max_gpus=32)
    pol = Policy(recon)
    pol.prewarm(spec, 25.0)
    res = ClusterSimulator(spec, pol, recon, arr,
                           SimConfig(duration_s=120.0,
                                     whole_gpu_cost=whole)).run()
    v = res.violations([1.5, 2.0, 2.5])
    print(f"{name:18s} cost/1k=${res.cost_per_1k:.4f}  "
          f"p95={res.pcts['p95']*1e3:6.0f} ms  "
          f"viol@1.5x/2x/2.5x = {v[1.5]:.3f}/{v[2.0]:.3f}/{v[2.5]:.3f}")
