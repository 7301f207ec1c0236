"""libhas — the pod-side resource-control shim.

In the paper this is an LD_PRELOAD library interposing CUDA Driver API
calls (cuLaunchKernel / cuMemAlloc) to enforce the pod's time-token and
memory allocations. The TPU/JAX analogue intercepts at the jitted-step
dispatch boundary: the engine wraps every step call in
``LibHas.launch(...)``, which (a) pays the pod's GPU client for the
device time charged to it and (b) enforces the pod's HBM budget against
the compiled step's memory analysis.

What is charged, and when: ``PodEngine.step`` passes each launch the
seconds the device was measurably held since the last charge (from the
previous launch's dispatch, ``dispatched_at``, to the return of the sync
that brought its token to the host), and ``charge``s the batch's last
launch once the batch ends. The client books each charge where it
happened, so the pod sleeps only where its quota is spent.

Operator reading: ``tokens_acquired_s`` (seconds charged) against
``slept_s`` (seconds the acquires slept) against the device time of the
launched steps says how far the charge, and not the chip, paces the pod.
Each acquire runs inside a ``libhas.acquire`` profiler span.
"""
from __future__ import annotations

import dataclasses
import time
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
    dispatched_at: float = 0.0       # monotonic time the last launch ran

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

    def charge(self, cost_s: float) -> None:
        """Pays the client for ``cost_s`` seconds of device time; sleeps
        as long as the client's acquire does."""
        with jax.profiler.TraceAnnotation("libhas.acquire"):
            self.slept_s += self.client.acquire(cost_s) or 0.0
        self.tokens_acquired_s += cost_s

    def launch(self, fn, *args, cost_s: Optional[float] = None, **kw):
        """cuLaunchKernel-interception analogue: charge ``cost_s``, then
        run, stamping ``dispatched_at`` after the acquire returns."""
        if cost_s is None and self.cost_estimator is not None:
            cost_s = self.cost_estimator(*args, **kw)
        if cost_s is not None:
            self.charge(cost_s)
        self.launches += 1
        self.dispatched_at = time.monotonic()
        return fn(*args, **kw)
