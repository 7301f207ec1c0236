"""The SSM path as ``PodEngine`` serves it: the recurrent-state bytes a
batch's record counts, and the named scopes of the SSD mixer, which must
not move a bit of its output."""
import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import ARCHS, reduced
from repro.core.scheduler import HASGPUScheduler
from repro.core.vgpu import PodAlloc, VirtualGPU
from repro.models import ssm
from repro.serving import InferenceRequest, PodEngine

SCOPES = ("ssm.conv", "ssm.chunk_scan", "ssm.state_update")


def _served_record(name, rows=3):
    cfg = reduced(ARCHS[name])
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    vgpu = VirtualGPU("GPU-ssm", window_ms=20.0)
    pod = PodAlloc(fn_id="f", sm=vgpu.gpu_type.sm_total, quota=1.0,
                   batch=rows)
    vgpu.place(pod)
    eng = PodEngine(cfg, pod, vgpu, HASGPUScheduler(), max_seq=32,
                    params=params)
    rng = np.random.default_rng(0)
    for _ in range(rows):
        eng.submit(InferenceRequest(
            prompt=rng.integers(2, cfg.vocab_size, size=6).astype(np.int32),
            max_new_tokens=2, arrival=time.monotonic() - 1.0))
    done = eng.step()
    assert len(done) == rows
    return cfg, done[0].batch_record


def test_batch_record_counts_the_recurrent_state():
    cfg, rec = _served_record("mamba2-2.7b")
    di, nh, conv_ch = ssm.ssm_dims(cfg)
    s = cfg.ssm
    conv = cfg.num_layers * 3 * (s.conv_width - 1) * conv_ch * 2   # bf16
    state = cfg.num_layers * 3 * nh * s.head_dim * s.d_state * 4  # f32
    assert rec.state_bytes == conv + state > 0


def test_batch_record_state_is_zero_without_recurrent_state():
    _, rec = _served_record("olmo-1b")
    assert rec.state_bytes == 0


@contextlib.contextmanager
def _no_scope(name):
    yield


@pytest.fixture(scope="module")
def mixer():
    cfg = reduced(ARCHS["mamba2-2.7b"])
    cfg = dataclasses.replace(
        cfg, ssm=dataclasses.replace(cfg.ssm, chunk_size=4))  # 4 chunks
    p = ssm.init_ssm(jax.random.PRNGKey(1), cfg)
    di, nh, conv_ch = ssm.ssm_dims(cfg)
    k = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(k[0], (2, 16, cfg.d_model), jnp.bfloat16)
    conv = jax.random.normal(k[1], (2, cfg.ssm.conv_width - 1, conv_ch),
                             jnp.bfloat16)
    h = jax.random.normal(k[2], (2, nh, cfg.ssm.head_dim, cfg.ssm.d_state))
    return cfg, p, x, conv, h


def _outputs(cfg, p, x, conv, h):
    fwd = jax.jit(lambda p, x, h: ssm.ssd_forward(
        cfg, p, x, initial_state=h, return_state=True))
    dec = jax.jit(lambda p, x, c, h: ssm.ssd_decode_step(cfg, p, x, c, h))
    return jax.tree.leaves((fwd(p, x, h), dec(p, x[:, :1], conv, h)))


def test_named_scopes_leave_the_numerics_bit_identical(mixer, monkeypatch):
    """The mixer with its scopes against the same functions traced with
    ``jax.named_scope`` made a no-op; the scopes reach the lowered
    program in the one and not in the other."""
    cfg, p, x, conv, h = mixer

    def hlo():
        return (jax.jit(lambda p, x, h: ssm.ssd_forward(
            cfg, p, x, initial_state=h)).lower(p, x, h).as_text(
                debug_info=True)
            + jax.jit(lambda p, x, c, h: ssm.ssd_decode_step(
                cfg, p, x, c, h)).lower(p, x[:, :1], conv, h).as_text(
                    debug_info=True))
    scoped, text = _outputs(cfg, p, x, conv, h), hlo()
    assert all(name in text for name in SCOPES)
    monkeypatch.setattr(jax, "named_scope", _no_scope)
    plain, bare = _outputs(cfg, p, x, conv, h), hlo()
    assert not any(name in bare for name in SCOPES)
    assert len(scoped) == len(plain) == 6
    for a, b in zip(scoped, plain):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
