"""libhas — the pod-side resource-control shim.

In the paper this is an LD_PRELOAD library interposing CUDA Driver API
calls (cuLaunchKernel / cuMemAlloc) to enforce the pod's time-token and
memory allocations. The TPU/JAX analogue intercepts at the jitted-step
dispatch boundary: the engine wraps every step call in
``LibHas.launch(...)``, which (a) acquires time tokens from the pod's GPU
client and (b) enforces the pod's HBM budget against the compiled step's
memory analysis.

Operator reading: ``tokens_acquired_s`` (seconds charged) against
``slept_s`` (seconds the acquires slept) against the device time of the
launched steps says how far the charge, and not the chip, paces the pod.
Each acquire runs inside a ``libhas.acquire`` profiler span.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax

from repro.core.scheduler import GPUClient


class MemoryBudgetExceeded(RuntimeError):
    pass


@dataclasses.dataclass
class LibHas:
    client: GPUClient
    hbm_budget_bytes: Optional[int] = None
    cost_estimator: Optional[Callable[..., float]] = None
    launches: int = 0
    tokens_acquired_s: float = 0.0   # seconds charged
    slept_s: float = 0.0             # seconds the acquires slept

    def check_memory(self, compiled) -> None:
        """cuMemAlloc-interception analogue: reject steps whose compiled
        footprint exceeds the pod's budget. The footprint is the full
        resident set of one step — arguments, scratch, AND outputs
        (outputs are live allocations the step must fit alongside its
        inputs; counting only args+temp under-reserved by the output
        size and let over-budget steps through)."""
        if self.hbm_budget_bytes is None:
            return
        m = compiled.memory_analysis()
        need = (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes)
        if need > self.hbm_budget_bytes:
            raise MemoryBudgetExceeded(
                f"step needs {need} B > budget {self.hbm_budget_bytes} B")

    def launch(self, fn, *args, cost_s: Optional[float] = None, **kw):
        """cuLaunchKernel-interception analogue: acquire tokens, then run."""
        if cost_s is None and self.cost_estimator is not None:
            cost_s = self.cost_estimator(*args, **kw)
        if cost_s is not None:
            with jax.profiler.TraceAnnotation("libhas.acquire"):
                self.slept_s += self.client.acquire(cost_s) or 0.0
            self.tokens_acquired_s += cost_s
        self.launches += 1
        return fn(*args, **kw)
