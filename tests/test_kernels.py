"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (interpret
mode on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

rng = np.random.default_rng(0)


def arr(*s, dtype=jnp.bfloat16, scale=1.0):
    return jnp.asarray(rng.standard_normal(s) * scale, dtype)


def rel_err(a, b):
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))


@pytest.mark.parametrize("B,S,T,K,G,hd", [
    (1, 128, 128, 1, 1, 64),
    (2, 256, 256, 2, 2, 64),
    (1, 128, 128, 2, 4, 128),
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention(B, S, T, K, G, hd, dtype, causal, window):
    q = arr(B, S, K, G, hd, dtype=dtype)
    k = arr(B, T, K, hd, dtype=dtype)
    v = arr(B, T, K, hd, dtype=dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    assert out.shape == want.shape and out.dtype == want.dtype
    assert rel_err(out, want) < (0.03 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("B,T,K,G,hd,pos", [
    (2, 128, 2, 2, 64, 100),
    (1, 256, 1, 8, 128, 10),
    (4, 64, 4, 1, 64, 63),
    (1, 1024, 2, 8, 128, 700),   # qwen2.5-3b widths at T=1024, many blocks
])
def test_decode_attention(B, T, K, G, hd, pos):
    q = arr(B, 1, K, G, hd)
    k = arr(B, T, K, hd)
    v = arr(B, T, K, hd)
    valid = jnp.asarray(np.arange(T) <= pos)
    out = ops.decode_attention(q, k, v, valid, block_k=64)
    want = ref.decode_attention_ref(q, k, v, valid)
    assert rel_err(out, want) < 0.03


@pytest.mark.parametrize("nc,B,Q,nh,hd,N", [
    (2, 1, 32, 2, 32, 16),
    (4, 2, 64, 4, 64, 32),
    (8, 1, 16, 1, 64, 128),
])
def test_ssd_chunk_scan(nc, B, Q, nh, hd, N):
    xc = arr(nc, B, Q, nh, hd, dtype=jnp.float32, scale=0.2)
    Bc = arr(nc, B, Q, nh, N, dtype=jnp.float32, scale=0.2)
    Cc = arr(nc, B, Q, nh, N, dtype=jnp.float32, scale=0.2)
    dtc = jnp.abs(arr(nc, B, Q, nh, dtype=jnp.float32, scale=0.05))
    dAc = -jnp.abs(arr(nc, B, Q, nh, dtype=jnp.float32, scale=0.1))
    h0 = jnp.asarray(rng.standard_normal((B, nh, hd, N)) * 0.1, jnp.float32)
    hk, yk = ops.ssd_chunk_scan(xc, Bc, Cc, dtc, dAc, h0)
    hr, yr = ref.ssd_chunk_scan_ref(xc, Bc, Cc, dtc, dAc, h0)
    assert rel_err(yk, yr) < 1e-4
    assert rel_err(hk, hr) < 1e-4


@pytest.mark.parametrize("E,C,K,N", [(2, 64, 128, 64), (4, 128, 64, 96),
                                     (1, 32, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gmm(E, C, K, N, dtype):
    x = arr(E, C, K, dtype=dtype)
    w = arr(E, K, N, dtype=dtype)
    out = ops.gmm(x, w, block_c=32, block_n=32, block_k=64)
    want = ref.gmm_ref(x, w)
    assert rel_err(out, want) < (0.02 if dtype == jnp.bfloat16 else 1e-5)


def test_gmm_default_blocks_tile_unaligned_k():
    """deepseek-moe's expert width K=1408 takes 128-wide K blocks (512
    does not divide it) and still matches the oracle."""
    from repro.kernels.moe_gmm import _fit
    assert _fit(1408, 512, 128) == 128
    assert _fit(2048, 512, 128) == 512
    assert _fit(96, 32, 128) == 96      # no aligned divisor: whole dim
    assert _fit(200, 128, 8) == 40
    x = arr(2, 16, 1408, dtype=jnp.float32)
    w = arr(2, 1408, 256, dtype=jnp.float32, scale=1408 ** -0.5)
    assert rel_err(ops.gmm(x, w), ref.gmm_ref(x, w)) < 1e-5


def test_expert_ffn():
    G, E, C, d, f = 2, 2, 32, 64, 128
    xe = arr(G, E, C, d)
    wg, wu = arr(E, d, f, scale=0.3), arr(E, d, f, scale=0.3)
    wd = arr(E, f, d, scale=0.3)
    out = ops.expert_ffn(xe, wg, wu, wd, "silu", block_c=32, block_n=32,
                         block_k=32)
    want = ref.expert_ffn_ref(xe, wg, wu, wd, "silu")
    assert rel_err(out, want) < 0.05
