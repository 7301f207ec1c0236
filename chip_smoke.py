"""Smoke test of the served path on one TPU chip.

Serves qwen2.5-3b at its published widths (random weights from a seed)
through the normal entry points: a ``PodAlloc`` placed on a
``VirtualGPU`` under the ``HASGPUScheduler``, a ``PodEngine`` registered
on a ``Gateway``, requests routed and pumped to completion. It serves
twice with the same weights, once through XLA and once through the
Pallas kernels, checks every request's output ids and compares the
first-token logits of the two runs. Then it runs each Pallas kernel at
the widths of the configurations that use it against its
``kernels/ref.py`` oracle in float32, and checks that none of them ran
in interpret mode.

Run from the root of a checkout, on a machine with a TPU, in one process
(a second process cannot reach the chip while this one holds it):

    python chip_smoke.py

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Any failed check exits non-zero without that line; so does a machine
where JAX finds no TPU. The compile cache sits where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Callable, List

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import models  # noqa: E402
from repro.configs import ARCHS  # noqa: E402
from repro.core.scheduler import HASGPUScheduler  # noqa: E402
from repro.core.vgpu import PodAlloc, VirtualGPU  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.models import CallOpts  # noqa: E402
from repro.serving import Gateway, InferenceRequest, PodEngine  # noqa: E402
from repro.serving.engine import compiled_steps  # noqa: E402

ARCH = "qwen2.5-3b"
MAX_SEQ = 1024           # the decode kernel streams a 1024-slot cache
PROMPT_LENS = (128, 256)  # one length per batch, two compiles per path
BATCH = 4
N_REQUESTS = 8
NEW_TOKENS = 16
SEED = 0
# Kernel vs XLA first-token logits, both in bfloat16: max |diff| over
# max |XLA logit|. bf16 keeps 8 mantissa bits (2^-8 ~ 4e-3 per rounding),
# and the two paths round attention at different points in each of 36
# layers; 5e-2 is the bound the CPU kernel-vs-reference model test uses.
LOGITS_RTOL = 5e-2
# Each kernel vs its float32 oracle at highest matmul precision:
# bf16 inputs and outputs, f32 accumulation inside the kernel.
KERNEL_RTOL = 2e-2


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL {msg}")


def rel_err(got, want) -> float:
    """max |got - want| / max |want|, in float32."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-9))


def init_params(cfg, seed: int = SEED):
    """Random weights for ``cfg``, made on the device in one program."""
    return jax.jit(models.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)


# ------------------------------------------------------------ serving
def serve(cfg, params, opts: CallOpts, *, max_seq: int = MAX_SEQ,
          prompt_lens=PROMPT_LENS, batch: int = BATCH,
          n_requests: int = N_REQUESTS, new_tokens: int = NEW_TOKENS,
          seed: int = SEED) -> dict:
    """Serve ``n_requests`` through Gateway -> PodEngine and check them.

    Requests are routed in batches of ``batch`` that share one prompt
    length, cycling through ``prompt_lens``. Fails unless every request
    returns ``new_tokens`` ids in ``[0, vocab)``. Returns the requests,
    the wall time of each served batch and the first-token logits
    (f32, ``(batch, vocab)``) of the first batch."""
    vgpu = VirtualGPU("TPU-0", window_ms=50.0)
    pod = PodAlloc(fn_id=f"fn-{cfg.name}", sm=vgpu.gpu_type.sm_total,
                   quota=1.0, batch=batch)
    vgpu.place(pod)
    gw = Gateway()
    gw.register(pod.fn_id, PodEngine(cfg, pod, vgpu, HASGPUScheduler(),
                                     max_seq=max_seq, params=params,
                                     opts=opts))
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        n = prompt_lens[(i // batch) % len(prompt_lens)]
        reqs.append(InferenceRequest(
            prompt=rng.integers(1, cfg.vocab_size, n).astype(np.int32),
            max_new_tokens=new_tokens))
        gw.route(pod.fn_id, reqs[-1])

    done: List[InferenceRequest] = []
    batch_s = []
    while len(done) < n_requests:
        t0 = time.perf_counter()
        got = gw.pump(pod.fn_id)
        if got:
            batch_s.append(time.perf_counter() - t0)
            done.extend(got)
    for r in reqs:
        out = r.output
        if out is None or out.shape != (new_tokens,):
            fail(f"request {r.req_id}: output "
                 f"{None if out is None else out.shape}, want "
                 f"({new_tokens},)")
        if out.min() < 0 or out.max() >= cfg.vocab_size:
            fail(f"request {r.req_id}: ids outside [0, {cfg.vocab_size})")

    prefill, _ = compiled_steps(cfg, max_seq, opts)  # the engine's own jit
    first = reqs[:batch]
    tokens = jnp.asarray(np.stack([r.prompt for r in first]))
    logits, _ = prefill(params, {"tokens": tokens})
    return {"requests": reqs, "batch_s": batch_s,
            "first_logits": np.asarray(logits[:, -1], np.float32)}


def pallas_calls_on_path(cfg, params, opts: CallOpts, max_seq: int,
                         prompt_len: int, batch: int = BATCH) -> dict:
    """Whether the engine's jitted prefill and decode lower to Mosaic
    kernels (``tpu_custom_call``) rather than interpreted Pallas."""
    prefill, decode = compiled_steps(cfg, max_seq, opts)
    tokens = jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)
    logits, cache = jax.eval_shape(prefill, params, {"tokens": tokens})
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return {
        "prefill": "tpu_custom_call" in prefill.lower(
            params, {"tokens": tokens}).as_text(),
        "decode": "tpu_custom_call" in decode.lower(
            params, tok, pos, cache).as_text(),
    }


# ------------------------------------------------------------ kernels
@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One kernel at the widths of a configuration that uses it."""
    name: str
    kernel: Callable      # (*inputs, interpret=None) -> output(s)
    oracle: Callable      # (*inputs) -> output(s), kernels/ref.py
    make: Callable        # PRNG key -> inputs


def _normal(key, shape, dtype, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _attn_inputs(B, S, T, K, G, hd):
    def make(key):
        kq, kk, kv = jax.random.split(key, 3)
        bf = jnp.bfloat16
        return (_normal(kq, (B, S, K, G, hd), bf),
                _normal(kk, (B, T, K, hd), bf), _normal(kv, (B, T, K, hd), bf))
    return make


def _decode_inputs(B, T, K, G, hd, pos):
    def make(key):
        q, k, v = _attn_inputs(B, 1, T, K, G, hd)(key)
        return q, k, v, jnp.arange(T) <= pos
    return make


def _gmm_inputs(E, C, K, N):
    def make(key):
        kx, kw = jax.random.split(key)
        return (_normal(kx, (E, C, K), jnp.bfloat16),
                _normal(kw, (E, K, N), jnp.bfloat16, K ** -0.5))
    return make


def _ssd_inputs(nc, B, Q, nh, hd, N):
    def make(key):
        ks = jax.random.split(key, 6)
        f32 = jnp.float32
        return (_normal(ks[0], (nc, B, Q, nh, hd), f32, 0.2),
                _normal(ks[1], (nc, B, Q, nh, N), f32, 0.2),
                _normal(ks[2], (nc, B, Q, nh, N), f32, 0.2),
                jnp.abs(_normal(ks[3], (nc, B, Q, nh), f32, 0.05)),
                -jnp.abs(_normal(ks[4], (nc, B, Q, nh), f32, 0.1)),
                _normal(ks[5], (B, nh, hd, N), f32, 0.1))
    return make


def kernel_cases() -> List[KernelCase]:
    """Every Pallas kernel at the published widths of its configuration."""
    qwen, olmo = ARCHS["qwen2.5-3b"], ARCHS["olmo-1b"]
    moe, mamba = ARCHS["deepseek-moe-16b"], ARCHS["mamba2-2.7b"]
    qk, qg = qwen.num_kv_heads, qwen.num_heads // qwen.num_kv_heads
    s = mamba.ssm
    nh = s.n_heads(mamba.d_model)
    return [
        KernelCase("flash qwen2.5-3b prefill B4 S512", ops.flash_attention,
                   ref.flash_attention_ref,
                   _attn_inputs(4, 512, 512, qk, qg, qwen.head_dim)),
        KernelCase("flash olmo-1b prefill B4 S512", ops.flash_attention,
                   ref.flash_attention_ref,
                   _attn_inputs(4, 512, 512, olmo.num_kv_heads, 1,
                                olmo.head_dim)),
        KernelCase("decode qwen2.5-3b B4 T1024", ops.decode_attention,
                   ref.decode_attention_ref,
                   _decode_inputs(4, 1024, qk, qg, qwen.head_dim, 700)),
        KernelCase("decode qwen2.5-3b B4 T2048", ops.decode_attention,
                   ref.decode_attention_ref,
                   _decode_inputs(4, 2048, qk, qg, qwen.head_dim, 4000)),
        KernelCase("gmm deepseek-moe-16b up", ops.gmm, ref.gmm_ref,
                   _gmm_inputs(moe.moe.num_experts, 128, moe.d_model,
                               moe.d_ff)),
        KernelCase("gmm deepseek-moe-16b down", ops.gmm, ref.gmm_ref,
                   _gmm_inputs(moe.moe.num_experts, 128, moe.d_ff,
                               moe.d_model)),
        KernelCase("ssd mamba2-2.7b 4 chunks", ops.ssd_chunk_scan,
                   ref.ssd_chunk_scan_ref,
                   _ssd_inputs(4, 1, s.chunk_size, nh, s.head_dim,
                               s.d_state)),
    ]


def check_kernel(case: KernelCase, seed: int = SEED) -> float:
    """Run ``case`` on the default device against its f32 oracle; returns
    the worst relative error. Fails if the kernel lowers to anything but
    a Mosaic custom call or misses KERNEL_RTOL."""
    inputs = case.make(jax.random.PRNGKey(seed))
    fn = jax.jit(case.kernel)
    if "tpu_custom_call" not in fn.lower(*inputs).as_text():
        fail(f"{case.name}: no Mosaic kernel in the lowered program "
             "(interpret mode?)")
    got = jax.block_until_ready(fn(*inputs))
    f32 = [a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.floating)
           else a for a in inputs]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(case.oracle)(*f32)
    err = max(rel_err(g, w) for g, w in zip(jax.tree.leaves(got),
                                           jax.tree.leaves(want)))
    if not err <= KERNEL_RTOL:
        fail(f"{case.name}: rel err {err:.3e} > {KERNEL_RTOL}")
    return err


# ------------------------------------------------------------ main
def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    print(f"device {dev.device_kind} x{len(jax.devices())}, compile cache "
          f"{compile_cache.enable()}", flush=True)

    cfg = ARCHS[ARCH]
    print(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.num_heads} heads / {cfg.num_kv_heads} kv, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab_size}, max_seq {MAX_SEQ}", flush=True)
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"params {n_bytes / 1e9:.3f} GB, init {time.perf_counter() - t0:.1f}"
          " s (compile included)", flush=True)

    runs = {}
    for label, opts in (("xla", CallOpts()),
                        ("pallas", CallOpts(use_kernels=True))):
        on_path = pallas_calls_on_path(cfg, params, opts, MAX_SEQ,
                                       PROMPT_LENS[0])
        if opts.use_kernels != on_path["prefill"] or \
                opts.use_kernels != on_path["decode"]:
            fail(f"{label}: Mosaic kernels on path {on_path}, "
                 f"want {opts.use_kernels}")
        run = runs[label] = serve(cfg, params, opts)
        lat = sorted(r.latency for r in run["requests"])
        print(f"[{label}] served {len(lat)} requests x {NEW_TOKENS} tokens; "
              f"batch wall s {[round(s, 3) for s in run['batch_s']]} "
              f"(first use of each prompt length compiles); latency s "
              f"min {lat[0]:.3f} max {lat[-1]:.3f}", flush=True)

    err = rel_err(runs["pallas"]["first_logits"], runs["xla"]["first_logits"])
    same = float(np.mean(runs["pallas"]["first_logits"].argmax(-1)
                         == runs["xla"]["first_logits"].argmax(-1)))
    print(f"first-token logits pallas vs xla: rel err {err:.3e} "
          f"(limit {LOGITS_RTOL}), argmax agreement {same:.2f}", flush=True)
    if not err <= LOGITS_RTOL:
        fail(f"kernel vs XLA logits rel err {err:.3e} > {LOGITS_RTOL}")

    for case in kernel_cases():
        print(f"kernel {case.name}: rel err vs f32 oracle "
              f"{check_kernel(case):.3e} (limit {KERNEL_RTOL})", flush=True)

    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"peak HBM in use {stats['peak_bytes_in_use'] / 1e9:.3f} GB")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
