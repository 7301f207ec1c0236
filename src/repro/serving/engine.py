"""Serving engine: real JAX prefill/decode under HAS resource control.

One ``PodEngine`` is a function instance: jitted prefill + decode steps
for its architecture, a batcher, and a libhas shim that acquires time
tokens sized by the pod's (sm, quota) before every dispatch. The engine
holds one whole model on the default device: ``chip_smoke.py`` serves
qwen2.5-3b at its published widths on one TPU v5e, and CPU tests serve
``reduced()`` configs through the same dispatch path (batch -> prefill
-> n x decode).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional

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
from repro.serving.batcher import Batcher, InferenceRequest
from repro.serving.libhas import LibHas
from repro.training import steps


@functools.lru_cache(maxsize=None)
def compiled_steps(cfg: ArchConfig, max_seq: int, opts: CallOpts) -> tuple:
    """Shared jitted ``(prefill, decode)`` steps for one architecture.

    Pods of the same function differ only in (sm, quota, batch) — none
    of which affect compilation — so every engine of a fn shares one
    jit cache instead of re-tracing per pod (the profiling harness
    sweeps many (sm, quota) points per arch and rides on this too)."""
    return (jax.jit(steps.make_prefill_step(cfg, max_seq, opts)),
            jax.jit(steps.make_decode_step(cfg, opts)))


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
        self.completed: List[InferenceRequest] = []

    # cost of one dispatch in *owned accelerator seconds* for this pod,
    # on the chip actually hosting it — charging at reference-device
    # physics would over-token fast chips and under-token slow ones
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
        """Serve one batch if ready. Returns completed requests."""
        if not self.batcher.ready():
            return []
        reqs = self.batcher.next_batch()
        prompts = self.batcher.pad_prompts(reqs, pad_id=self.batcher.pad_id,
                                           pad_to=None)
        B, L = prompts.shape
        v = self.cfg.num_visual_tokens or 0
        batch = {"tokens": jnp.asarray(prompts), **self._extra_inputs(B)}
        logits, cache = self.libhas.launch(
            self._prefill, self.params, batch, cost_s=self._cost(B * L))
        n_new = max(r.max_new_tokens for r in reqs)
        outs = np.zeros((B, n_new), np.int32)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        for i in range(n_new):
            outs[:, i] = np.asarray(tok[:, 0])
            pos = jnp.asarray(v + L + i, jnp.int32)
            logits, cache = self.libhas.launch(
                self._decode, self.params, tok, pos, cache,
                cost_s=self._cost(B))
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        now = time.monotonic()
        for j, r in enumerate(reqs):
            r.output = outs[j, :r.max_new_tokens]
            r.completed_at = now
        self.completed.extend(reqs)
        return reqs

    def set_quota(self, vgpu: VirtualGPU, quota: float) -> None:
        """Vertical scaling at runtime: next token acquisition sees it."""
        vgpu.set_quota(self.pod.pod_id, quota)
