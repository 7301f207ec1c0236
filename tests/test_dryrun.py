"""Dry-run machinery tests.

The full 512-device sweep runs via ``python -m repro.launch.dryrun``;
here we validate the HLO analyzer's exactness and one real combo through
a subprocess (so the XLA device-count flag does not leak into this test
process, which must keep seeing 1 device).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis as ha

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_analyzer_scan_equals_unroll():
    D, L, B = 256, 8, 4
    w = jax.ShapeDtypeStruct((L, D, D), jnp.bfloat16)
    x = jax.ShapeDtypeStruct((B, D), jnp.bfloat16)

    def scanned(w, x):
        def f(h, wl):
            return h @ wl, None
        h, _ = jax.lax.scan(f, x, w)
        return h

    def unrolled(w, x):
        h = x
        for i in range(L):
            h = h @ w[i]
        return h

    a_scan = ha.analyze(jax.jit(scanned).lower(w, x).compile().as_text())
    a_unroll = ha.analyze(jax.jit(unrolled).lower(w, x).compile().as_text())
    analytic = 2.0 * B * D * D * L
    assert a_scan.flops == pytest.approx(analytic, rel=1e-6)
    assert a_unroll.flops == pytest.approx(analytic, rel=1e-6)
    assert not a_scan.unknown_trip_whiles


def test_analyzer_collectives():
    # single-device: no collectives expected
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    a = ha.analyze(jax.jit(lambda t: t @ t).lower(x).compile().as_text())
    assert a.collective_bytes == 0.0
    assert a.flops == pytest.approx(2 * 64**3, rel=1e-6)


@pytest.mark.slow
def test_dryrun_one_combo_subprocess():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "olmo-1b",
         "--shape", "decode_32k", "--out", "/tmp/dryrun_test"],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "ALL DRY-RUN COMBOS PASSED" in out.stdout


def test_device_count_not_polluted():
    assert len(jax.devices()) == 1
