"""Grouped (per-expert) blocked matmul Pallas TPU kernel.

gmm(x (E,C,K), w (E,K,N)) -> (E,C,N): grid = (E, C/bc, N/bn, K/bk) with
the K-reduction innermost accumulating into an f32 VMEM scratch tile, the
canonical MXU-blocked matmul. ``expert_ffn`` composes three gmm calls into
the gated expert FFN used by the einsum-dispatch MoE layer — the dispatch
one-hots stay in XLA; the expert compute hot loop is the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_sc, *, n_k):
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    x = x_ref[0].astype(jnp.float32)   # (bc, bk)
    w = w_ref[0].astype(jnp.float32)   # (bk, bn)
    acc_sc[...] += x @ w

    @pl.when(kk == n_k - 1)
    def _done():
        o_ref[0] = acc_sc[...].astype(o_ref.dtype)


def _fit(dim: int, pref: int, align: int) -> int:
    """Largest block <= ``pref`` that divides ``dim`` and is a multiple of
    ``align``; the whole dimension when none is (always a legal block).
    E.g. deepseek-moe's K=1408=11*128 takes 128 where 512 would not tile."""
    if dim <= pref:
        return dim
    for b in range(pref - pref % align, 0, -align):
        if dim % b == 0:
            return b
    return dim


@functools.partial(jax.jit, static_argnames=("block_c", "block_n", "block_k",
                                             "interpret"))
def gmm(x, w, *, block_c=128, block_n=128, block_k=512, interpret=None):
    """x: (E, C, K) @ w: (E, K, N) -> (E, C, N).

    Blocks are the preferred sizes shrunk to divisors of the dimensions
    that obey the TPU tiling: multiples of 8 for C, of 128 for N and K."""
    E, C, K = x.shape
    N = w.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bc, bn, bk = _fit(C, block_c, 8), _fit(N, block_n, 128), \
        _fit(K, block_k, 128)
    grid = (E, C // bc, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, n_k=K // bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, bk), lambda e, i, j, kk: (e, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda e, i, j, kk: (e, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bn), lambda e, i, j, kk: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bn), jnp.float32)],
        interpret=interpret,
    )(x, w)


def expert_ffn(xe, w_gate, w_up, w_down, act="silu", **kw):
    """xe: (G, E, C, d) -> (G, E, C, d) via per-expert gated FFN."""
    G, E, C, d = xe.shape
    f = w_gate.shape[-1]
    x = xe.transpose(1, 0, 2, 3).reshape(E, G * C, d)
    a = jax.nn.silu if act == "silu" else (
        lambda t: jax.nn.gelu(t, approximate=True))
    h = a(gmm(x, w_gate, **kw)) * gmm(x, w_up, **kw)
    y = gmm(h.astype(xe.dtype), w_down, **kw)
    return y.reshape(E, G, C, d).transpose(1, 0, 2, 3)
