"""One run of one cell: set-up, the measured window, the drain and the
output check, through the program's normal serving objects.

Set-up places one ``PodAlloc`` (the mix's quota, every slice, the mix's
batch) on a ``VirtualGPU`` under the ``HASGPUScheduler``, registers a
``PodEngine`` with the default ``CallOpts()`` on a ``Gateway``, makes the
weights from the seed and warms every batch size the mix can form at its
prompt length, through ``PodEngine.step`` itself.

The window drives ``Gateway.route`` and ``Gateway.pump`` in one loop.
Before each pump it routes every request whose due time has passed, with
``arrival`` set to that due time, so a pump that blocks is charged to the
requests that waited behind it. Requests due in the window are drained
after it closes; one not completed by the drain deadline has failed. A
backlog mix keeps ``2 * batch`` requests queued and counts the requests
of the batches started in the window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import shutil
import tempfile
import time
from collections import deque
from typing import Callable, List, Optional

import numpy as np

from bench import trace as trace_mod
from bench import traffic as traffic_mod
from bench.spec import Cell

DRAIN_S = 90.0       # deadline after the window closes
IDLE_SLEEP_S = 5e-4  # loop sleep while the batcher is not ready
# Traced span at the window's end. A 51 s trace of qwen2.5-3b's decode
# (~1,500 device operations a step) lost program events on a v5e: the
# decode programs in it no longer matched the steps served.
TRACE_S = 10.0


@dataclasses.dataclass
class Req:
    planned: traffic_mod.Planned
    due: float                       # host monotonic seconds
    request: object                  # the program's InferenceRequest
    pump_start: Optional[float] = None


@dataclasses.dataclass
class Batch:
    start: float                     # host monotonic, pump call
    end: float
    prompt_len: int
    n_new: int                       # decode steps: longest output
    reqs: List[Req]

    @property
    def size(self) -> int:
        return len(self.reqs)


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take what they need."""
    cell: Cell
    shape: object                    # the family's Shape
    peaks: dict                      # this device's row of peaks.json
    t_start: float                   # process start, host monotonic
    t_window: float                  # window opens
    t_last_end: float                # end of the last batch started in it
    attempted: List[Req]
    batches: List[Batch]             # started inside the window
    compiles_in_window: int
    memory_peak_bytes: Optional[int] = None
    trace: Optional[trace_mod.Trace] = None
    t_trace: Optional[float] = None  # traced span opens, host monotonic

    @property
    def failed(self) -> List[Req]:
        return [r for r in self.attempted if r.request.completed_at is None]

    @property
    def traced_batches(self) -> List[Batch]:
        """The window's batches whose programs lie in the traced span."""
        if self.t_trace is None:
            return []
        return [b for b in self.batches if b.start >= self.t_trace]


class CompileCounter:
    """Counts backend compiles while ``active`` (a ``jax.monitoring``
    listener on ``/jax/core/compile/backend_compile_duration``)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.active = False
        self.count = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if self.active and event == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


class _Unmetered:
    """A GPU client that grants every acquire at once: warm-up runs each
    shape through ``PodEngine.step`` without paying its charge."""

    def acquire(self, cost_s: float) -> None:
        return None


def seed_key(seed: int):
    import jax
    state = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(state) & 0x7FFFFFFF)


class Server:
    """The served path for one cell, built and warmed."""

    def __init__(self, cell: Cell, seed: int):
        import jax
        from repro.core.scheduler import HASGPUScheduler
        from repro.core.vgpu import PodAlloc, VirtualGPU
        from repro.serving import Gateway, PodEngine

        fam = cell.family
        self.cell = cell
        self.shape = fam.Shape.of(cell.config)
        self.cfg = fam.arch_config(cell.config)
        self.pod_spec = cell.traffic["pod"]
        self.weights = jax.block_until_ready(jax.jit(
            fam.make_weights, static_argnums=0)(self.shape, seed_key(seed)))
        vgpu = VirtualGPU("TPU-0")
        self.pod = PodAlloc(fn_id=f"fn-{self.cfg.name}",
                            sm=vgpu.gpu_type.sm_total,
                            quota=self.pod_spec["quota"],
                            batch=self.pod_spec["batch"])
        vgpu.place(self.pod)
        self.engine = PodEngine(
            self.cfg, self.pod, vgpu, HASGPUScheduler(),
            max_seq=self.pod_spec["max_seq"],
            params=fam.program_params(self.shape, self.weights))
        self.gateway = Gateway()
        self.gateway.register(self.pod.fn_id, self.engine)
        self.fn_id = self.pod.fn_id
        self._barrier = jax.jit(lambda x: x + 1)

    def barrier(self) -> None:
        """Waits until the device has run everything enqueued before."""
        import jax.numpy as jnp
        self._barrier(jnp.zeros((), jnp.int32)).block_until_ready()

    def route(self, planned: traffic_mod.Planned, due: float):
        from repro.serving import InferenceRequest
        req = InferenceRequest(prompt=planned.prompt,
                               max_new_tokens=planned.max_new, arrival=due)
        self.gateway.route(self.fn_id, req)
        return req

    def queued(self) -> int:
        return len(self.engine.batcher.queue)

    def warm(self, seed: int) -> None:
        """Runs every batch size the mix can form once, at its prompt
        length, unmetered: all of the cell's programs compile (or load
        from the cache) here and none in the window."""
        B = self.pod_spec["batch"]
        sizes = [B] if self.cell.traffic["arrivals"]["kind"] == "backlog" \
            else range(1, B + 1)
        rng = np.random.default_rng([int(seed) % 2**63, 9])
        L = self.cell.traffic["prompt_len"]
        libhas = self.engine.libhas
        self.engine.libhas = dataclasses.replace(libhas, client=_Unmetered())
        try:
            for b in sizes:
                past = time.monotonic() - 1.0
                for _ in range(b):
                    self.route(traffic_mod.Planned(
                        None, rng.integers(1, self.shape.vocab, L,
                                           dtype=np.int32), 1), past)
                while not self.gateway.pump(self.fn_id):
                    pass
            self.barrier()
        finally:
            self.engine.libhas = libhas


def _annotate(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(trace_mod.SPAN_PREFIX + name)


def serve_window(server: Server, seconds: float, seed: int,
                 counter: CompileCounter, traced: bool,
                 rate_per_s: Optional[float] = None,
                 clock: Callable[[], float] = time.monotonic) -> dict:
    """The measured window and the drain. Returns the attempted requests,
    the batches started in the window, the end of the last of them, and,
    when ``traced``, the trace directory and when the trace began.

    The trace covers the window's last ``TRACE_S`` seconds (from the first
    pump after that point to the end of the last batch started in the
    window) inside a ``bench.window`` span; it is written out after the
    drain, so that writing it delays no request."""
    import jax
    cell = server.cell
    B = server.pod_spec["batch"]
    backlog = cell.traffic["arrivals"]["kind"] == "backlog"
    vocab = server.shape.vocab
    source = (traffic_mod.backlog(cell.traffic, seed, vocab) if backlog
              else deque(traffic_mod.open_loop(cell.traffic, seconds, seed,
                                               vocab, rate_per_s)))
    attempted: List[Req] = []
    by_id = {}
    batches: List[Batch] = []
    log_dir, window_span, t_trace = None, None, None

    def pump(t_now: float) -> Optional[Batch]:
        with _annotate("pump", window_span is not None):
            got = server.gateway.pump(server.fn_id)
        if not got:
            return None
        end = clock()
        reqs = [by_id[r.req_id] for r in got]
        for r in reqs:
            r.pump_start = t_now
        return Batch(t_now, end, len(reqs[0].planned.prompt),
                     max(r.planned.max_new for r in reqs), reqs)

    t0 = clock()
    end = t0 + seconds
    counter.active = True
    while True:
        now = clock()
        if now >= end:
            break
        if traced and t_trace is None and now >= end - TRACE_S:
            log_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            window_span = _annotate("window", True)
            window_span.__enter__()
            t_trace = now = clock()
        with _annotate("route", window_span is not None):
            if backlog:
                while server.queued() < 2 * B:
                    p = next(source)
                    r = Req(p, now, server.route(p, now))
                    by_id[r.request.req_id] = r
            else:
                while source and t0 + source[0].due <= now:
                    p = source.popleft()
                    r = Req(p, t0 + p.due, server.route(p, t0 + p.due))
                    by_id[r.request.req_id] = r
                    attempted.append(r)
        if server.queued():
            b = pump(now)
            if b is not None:
                batches.append(b)
            else:
                time.sleep(IDLE_SLEEP_S)
        elif not backlog:
            wait = (t0 + source[0].due if source else end) - clock()
            time.sleep(min(max(wait, 0.0), end - now))
    counter.active = False
    if window_span is not None:
        server.barrier()
        window_span.__exit__(None, None, None)
        window_span = None
    queued_at_close = server.queued()
    t_last_end = batches[-1].end if batches else clock()
    if backlog:
        attempted = [r for b in batches for r in b.reqs]
    else:
        while source:
            p = source.popleft()
            r = Req(p, t0 + p.due, server.route(p, t0 + p.due))
            by_id[r.request.req_id] = r
            attempted.append(r)
        deadline = clock() + DRAIN_S
        while server.queued() and clock() < deadline:
            if pump(clock()) is None:
                time.sleep(IDLE_SLEEP_S)
    if log_dir is not None:
        jax.profiler.stop_trace()
    return {"t_window": t0, "t_last_end": t_last_end, "attempted": attempted,
            "batches": batches, "log_dir": log_dir, "t_trace": t_trace,
            "queued_at_close": queued_at_close}


def read_trace(log_dir: str) -> trace_mod.Trace:
    try:
        return trace_mod.load(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


# ------------------------------------------------------------ the check
def check_sample(attempted: List[Req], n: int, seed: int) -> List[Req]:
    """``n`` completed requests drawn from the seed, the one with the
    most served tokens always among them."""
    done = [r for r in attempted if r.request.completed_at is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: r.planned.max_new)
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) % 2**63, 3])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def output_faults(attempted: List[Req], vocab: int) -> int:
    """Completed requests whose output is not ``max_new`` ids in
    ``[0, vocab)``."""
    bad = 0
    for r in attempted:
        out = r.request.output
        if r.request.completed_at is None:
            continue
        if (out is None or out.shape != (r.planned.max_new,)
                or out.min() < 0 or out.max() >= vocab):
            bad += 1
    return bad


def sequences(sample: List[Req], max_seq: int) -> tuple:
    """Each sampled request's prompt and served tokens, padded to
    ``max_seq``, and the positions whose next token was served."""
    toks, spans = [], []
    for r in sample:
        seq = np.concatenate([r.planned.prompt,
                              np.asarray(r.request.output, np.int32)])
        pad = np.zeros(max_seq, np.int32)
        pad[:len(seq)] = seq[:max_seq]
        toks.append(pad)
        L = len(r.planned.prompt)
        spans.append((L - 1, L - 1 + r.planned.max_new))
    return toks, spans


def logit_gaps(family, shape, weights, sample: List[Req], max_seq: int,
               control: bool = False) -> dict:
    """Widest gap, over every served token of ``sample``, between the
    reference's best logit and its logit of the served token; with
    ``control``, also of the token the float8 control puts first."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(family.logit_gaps if control else family.served_gaps,
                 static_argnums=0)
    toks, spans = sequences(sample, max_seq)
    served, ctrl, n_tokens = 0.0, 0.0, 0
    for t, (a, b) in zip(toks, spans):
        out = fn(shape, weights, jnp.asarray(t))
        g = np.asarray(out[0] if control else out)[a:b]
        served = max(served, float(g.max()))
        n_tokens += b - a
        if control:
            ctrl = max(ctrl, float(np.asarray(out[1])[a:b].max()))
    res = {"served_gap": served, "tokens": n_tokens}
    if control:
        res["control_gap"] = ctrl
    return res
