"""Serving engine: real JAX prefill/decode under HAS resource control.

One ``PodEngine`` is a function instance: jitted prefill + decode steps
for its architecture, a batcher, and a libhas shim through which every
dispatch pays for device time. What is paid is measured, not predicted:
each launch's seconds from its dispatch to the return of the host sync
that brings its token, charged at the next launch (the batch's last
launch once the batch ends); the pod's quota of each time-token window
then decides whether the charge sleeps. The engine
holds one whole model on the default device: ``chip_smoke.py`` serves
qwen2.5-3b at its published widths on one TPU v5e, and CPU tests serve
``reduced()`` configs through the same dispatch path (batch -> prefill
-> n x decode).

``step`` runs inside ``jax.profiler.TraceAnnotation`` spans, which cost
about a microsecond each when no profiler is recording: ``engine.batch``
(with the batch id, rows, decode steps and request ids) around one
batch, and inside it ``engine.prefill`` and ``engine.decode`` around
each launch, ``engine.sample`` around each eager argmax and
``engine.sync`` around each host copy of a token and the wait for the
last decode; each libhas charge adds ``libhas.acquire``. Each served
request holds the batch's ``BatchRecord``, which also counts the bytes
of recurrent state in the batch's cache.
"""
from __future__ import annotations

import functools
import itertools
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs import ArchConfig
from repro.configs.gpus import DEFAULT_GPU_TYPE
from repro.core.perf_model import FnSpec, exec_time
from repro.core.scheduler import HASGPUScheduler
from repro.core.vgpu import PodAlloc, VirtualGPU
from repro.models import CallOpts
from repro.serving.batcher import Batcher, BatchRecord, InferenceRequest
from repro.serving.libhas import LibHas
from repro.training import steps


@functools.lru_cache(maxsize=None)
def compiled_steps(cfg: ArchConfig, max_seq: int, opts: CallOpts) -> tuple:
    """Shared jitted ``(prefill, decode)`` steps for one architecture.

    Pods of the same function differ only in (sm, quota, batch) — none
    of which affect compilation — so every engine of a fn shares one
    jit cache instead of re-tracing per pod (the profiling harness
    sweeps many (sm, quota) points per arch and rides on this too).

    The step functions' names are read by traces: their programs are
    ``jit_prefill_step`` and ``jit_decode_step``."""
    return (jax.jit(steps.make_prefill_step(cfg, max_seq, opts)),
            jax.jit(steps.make_decode_step(cfg, opts)))


def state_bytes(cache) -> int:
    """Bytes of the recurrent-state leaves (``conv``, ``state``) of a
    cache, from their shapes: no device sync."""
    return sum(leaf.size * leaf.dtype.itemsize
               for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
               if getattr(path[-1], "key", None) in ("conv", "state"))


def _sample(logits) -> jax.Array:
    """Greedy next token of each row, ``(B, 1) int32``, on the device."""
    with jax.profiler.TraceAnnotation("engine.sample"):
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]


class PodEngine:
    def __init__(self, cfg: ArchConfig, pod: PodAlloc, vgpu: VirtualGPU,
                 scheduler: HASGPUScheduler,
                 max_seq: int = 256, seed: int = 0,
                 params=None, opts: CallOpts = CallOpts(),
                 pad_id: int = 0):
        self.cfg = cfg
        self.pod = pod
        self.spec = FnSpec(cfg, seq=max_seq)
        self.max_seq = max_seq
        self.opts = opts
        self.params = params if params is not None else models.init_params(
            jax.random.PRNGKey(seed), cfg)
        client = scheduler.client_for(vgpu, pod.pod_id)
        self.libhas = LibHas(client=client)
        self.batcher = Batcher(max_batch=pod.batch, pad_id=pad_id)
        self._prefill, self._decode = compiled_steps(cfg, max_seq, opts)
        self._batch_ids = itertools.count()

    # the perf model's cost of one dispatch in *owned accelerator
    # seconds* for this pod, on the chip actually hosting it, for callers
    # that charge before a launch (the profiling harness); ``step``
    # charges measured time instead
    def _cost(self, n_tokens_equiv: int) -> float:
        gpu = self.pod.gpu_type or DEFAULT_GPU_TYPE
        t_full = exec_time(self.spec, max(self.pod.batch, 1), self.pod.sm,
                           gpu)
        return t_full * n_tokens_equiv / self.spec.seq

    def _extra_inputs(self, B):
        extra = {}
        if self.cfg.is_encoder_decoder:
            extra["frame_embeds"] = jnp.zeros(
                (B, self.cfg.encoder_seq, self.cfg.d_model), jnp.bfloat16)
        if self.cfg.num_visual_tokens:
            extra["visual_embeds"] = jnp.zeros(
                (B, self.cfg.num_visual_tokens, self.cfg.d_model),
                jnp.bfloat16)
        return extra

    def submit(self, req: InferenceRequest) -> None:
        self.batcher.submit(req)

    def step(self) -> List[InferenceRequest]:
        """Serve one batch if ready. Returns completed requests, each
        holding the batch's ``BatchRecord``."""
        if not self.batcher.ready():
            return []
        started = time.monotonic()
        reqs = self.batcher.next_batch()
        rec = BatchRecord(batch_id=next(self._batch_ids),
                          steps=max(r.max_new_tokens for r in reqs),
                          started=started)
        libhas = self.libhas
        slept0, charged0 = libhas.slept_s, libhas.tokens_acquired_s
        with jax.profiler.TraceAnnotation(
                "engine.batch", batch=rec.batch_id, rows=len(reqs),
                steps=rec.steps, reqs=" ".join(str(r.req_id) for r in reqs)):
            prompts = self.batcher.pad_prompts(
                reqs, pad_id=self.batcher.pad_id, pad_to=None)
            B, L = prompts.shape
            v = self.cfg.num_visual_tokens or 0
            batch = {"tokens": jnp.asarray(prompts), **self._extra_inputs(B)}
            # Each launch is charged what it held the device, from its
            # dispatch to the return of the sync that brings its token
            # (prefill: sync 0; decode i: sync i + 1), and pays at the next
            # launch. The last decode's token is never read: it is waited
            # for, and paid, once the loop ends.
            with jax.profiler.TraceAnnotation("engine.prefill"):
                logits, cache = libhas.launch(
                    self._prefill, self.params, batch, cost_s=0.0)
            rec.state_bytes = state_bytes(cache)
            tok = _sample(logits)
            outs = np.zeros((B, rec.steps), np.int32)
            for i in range(rec.steps):
                with jax.profiler.TraceAnnotation("engine.sync"):
                    outs[:, i] = np.asarray(tok[:, 0])
                synced, slept = time.monotonic(), libhas.slept_s
                busy = synced - libhas.dispatched_at
                pos = jnp.asarray(v + L + i, jnp.int32)
                with jax.profiler.TraceAnnotation("engine.decode"):
                    logits, cache = libhas.launch(
                        self._decode, self.params, tok, pos, cache,
                        cost_s=busy)
                rec.turnaround_s += (time.monotonic() - synced
                                     - (libhas.slept_s - slept))
                tok = _sample(logits)
            with jax.profiler.TraceAnnotation("engine.sync"):
                tok.block_until_ready()
            libhas.charge(time.monotonic() - libhas.dispatched_at)
            now = time.monotonic()
            rec.ended, rec.slept_s = now, libhas.slept_s - slept0
            rec.charged_s = libhas.tokens_acquired_s - charged0
            for j, r in enumerate(reqs):
                r.output = outs[j, :r.max_new_tokens]
                r.completed_at = now
                r.batch_record = rec
        return reqs

    def set_quota(self, vgpu: VirtualGPU, quota: float) -> None:
        """Vertical scaling at runtime: next token acquisition sees it."""
        vgpu.set_quota(self.pod.pod_id, quota)
