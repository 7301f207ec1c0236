"""Mamba2 SSD chunk-scan Pallas TPU kernel.

grid = (B x nh, n_chunks) with the chunk axis innermost: the SSM state
(hd, N) lives in an f32 VMEM scratch and carries across chunks (TPU grid
steps run sequentially per core). Each step computes the quadratic
intra-chunk dual form — (Q,Q) decay-masked C.B^T scores feeding the MXU —
plus the carried-state contribution, then advances the state. The (Q,Q)
working set is what the chunk size tunes against VMEM (Q=256 default:
256x256 f32 = 256 KiB).

Mosaic has no cumsum, so the wrapper takes the in-chunk cumulative sum
of dA in XLA and hands the kernel the per-step vectors as ``(Q, 1)``
columns (dt, cum) and a ``(1, Q)`` row (cum). Each block then spans its
array's last two dims, as the TPU tiling requires for any chunk count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, cum_ref, cumr_ref, h0_ref,
                y_ref, hout_ref, h_sc, *, Q, n_chunks):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        h_sc[...] = h0_ref[0].astype(jnp.float32)     # (hd, N)

    x = x_ref[0, 0].astype(jnp.float32)               # (Q, hd)
    Bm = b_ref[0, 0].astype(jnp.float32)              # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)              # (Q, N)
    dt = dt_ref[0, 0]                                 # (Q, 1)
    li = cum_ref[0, 0]                                # (Q, 1)
    lj = cumr_ref[0, 0]                               # (1, Q)
    # cum[Q-1] by a masked reduction: a lane slice at offset Q-1 has a
    # layout Mosaic cannot broadcast from
    last = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1
    total = jnp.sum(jnp.where(last, lj, 0.0), axis=1, keepdims=True)  # (1,1)

    cb = Cm @ Bm.T                                    # (Q, Q)
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    decay = jnp.exp(jnp.where(tri, li - lj, -1e30))
    y_intra = (cb * decay) @ (x * dt)                 # (Q, hd)
    h = h_sc[...]                                     # (hd, N)
    y_inter = (Cm * jnp.exp(li)) @ h.T                # (Q, hd)
    w = dt * jnp.exp(total - li)                      # (Q, 1)
    dstate = (x * w).T @ Bm                           # (hd, N)
    h_new = jnp.exp(total) * h + dstate
    h_sc[...] = h_new
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    @pl.when(j == n_chunks - 1)
    def _done():
        hout_ref[0] = h_new


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_scan(xc, Bc, Cc, dtc, dAc, h0, *, interpret=None):
    """xc: (nc,B,Q,nh,hd); Bc/Cc: (nc,B,Q,nh,N); dtc/dAc: (nc,B,Q,nh);
    h0: (B,nh,hd,N) f32. Returns (final (B,nh,hd,N) f32, y (nc,B,Q,nh,hd) f32).
    """
    nc, B, Q, nh, hd = xc.shape
    N = Bc.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    BH = B * nh
    xf = xc.transpose(1, 3, 0, 2, 4).reshape(BH, nc, Q, hd)
    bf = Bc.transpose(1, 3, 0, 2, 4).reshape(BH, nc, Q, N)
    cf = Cc.transpose(1, 3, 0, 2, 4).reshape(BH, nc, Q, N)
    dtf = dtc.astype(jnp.float32).transpose(1, 3, 0, 2).reshape(BH, nc, Q)
    cum = jnp.cumsum(dAc.astype(jnp.float32), axis=2)
    cumf = cum.transpose(1, 3, 0, 2).reshape(BH, nc, Q)
    h0f = h0.reshape(BH, hd, N)

    def per_chunk(last2):
        return pl.BlockSpec((1, 1) + last2, lambda bh, j: (bh, j, 0, 0))

    y, hout = pl.pallas_call(
        functools.partial(_ssd_kernel, Q=Q, n_chunks=nc),
        grid=(BH, nc),
        in_specs=[
            per_chunk((Q, hd)),
            per_chunk((Q, N)),
            per_chunk((Q, N)),
            per_chunk((Q, 1)),
            per_chunk((Q, 1)),
            per_chunk((1, Q)),
            pl.BlockSpec((1, hd, N), lambda bh, j: (bh, 0, 0)),
        ],
        out_specs=[
            per_chunk((Q, hd)),
            pl.BlockSpec((1, hd, N), lambda bh, j: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nc, Q, hd), jnp.float32),
            jax.ShapeDtypeStruct((BH, hd, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, N), jnp.float32)],
        interpret=interpret,
    )(xf, bf, cf, dtf[..., None], cumf[..., None], cumf[:, :, None, :], h0f)

    y = y.reshape(B, nh, nc, Q, hd).transpose(2, 0, 3, 1, 4)
    return hout.reshape(B, nh, hd, N), y
