"""Single-token (decode) GQA attention Pallas TPU kernel.

grid = (batch x kv_heads, k_blocks): each step loads a (block_k, hd) tile
of the KV cache ring buffer into VMEM, applies the validity mask (ring
fill state), and maintains the online-softmax carry for all G query heads
of the kv head at once — the (G, hd) query tile is small and stays
resident. This is the memory-bound kernel of batched decode: arithmetic
intensity ~= G, so block_k is chosen large (512) to stream the cache at
full HBM bandwidth. The mask travels as an int32 ``(1, T)`` row blocked
``(1, block_k)``: a 1-D block of it does not match the layout XLA gives
the mask once T exceeds 512.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _dec_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_sc, l_sc, acc_sc,
                *, scale, n_k):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32) * scale          # (G, hd)
    k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    ok = valid_ref[...] != 0                          # (1, bk)
    s = q @ k.T                                       # (G, bk)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + p.sum(axis=-1)
    acc_sc[...] = acc_sc[...] * corr[:, None] + p @ v
    m_sc[...] = m_new

    @pl.when(j == n_k - 1)
    def _done():
        o_ref[0] = (acc_sc[...] /
                    jnp.maximum(l_sc[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, valid, *, block_k=512, interpret=None):
    """q: (B,1,K,G,hd); k,v: (B,T,K,hd); valid: (T,) -> (B,1,K,G,hd)."""
    B, _, K, G, hd = q.shape
    T = k.shape[1]
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    block_k = min(block_k, T)
    assert T % block_k == 0
    n_k = T // block_k
    scale = 1.0 / (hd ** 0.5)

    qf = q.reshape(B, K, G, hd).reshape(B * K, G, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, T, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, T, hd)
    mask = valid.astype(jnp.int32).reshape(1, T)

    out = pl.pallas_call(
        functools.partial(_dec_kernel, scale=scale, n_k=n_k),
        grid=(B * K, n_k),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k), lambda bh, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda bh, j: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * K, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, mask)
    return out.reshape(B, 1, K, G, hd)
