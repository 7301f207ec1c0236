"""Mamba2 (SSD — state-space duality) block, chunked-scan formulation.

Reference: Dao & Gu, "Transformers are SSMs" (arXiv:2405.21060). The
sequence is split into chunks; within a chunk the SSD is computed in its
quadratic "attention-like" dual form (MXU-friendly einsums), and a
`lax.scan` carries the (heads, head_dim, state) SSM state across chunks.
The intra-chunk dual form has a Pallas TPU kernel in
``repro.kernels.ssd_scan``; this module is the jnp reference and the
dry-run lowering path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import common


def ssm_dims(cfg):
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    conv_ch = di + 2 * s.n_groups * s.d_state
    return di, nh, conv_ch


def init_ssm(rng, cfg):
    s = cfg.ssm
    d = cfg.d_model
    di, nh, conv_ch = ssm_dims(cfg)
    dt = common.dtype_of(cfg)
    ks = jax.random.split(rng, 5)
    proj_out = 2 * di + 2 * s.n_groups * s.d_state + nh  # z, x, B, C, dt
    # dt bias: inverse-softplus of dt ~ U[1e-3, 1e-1]
    u = jax.random.uniform(ks[2], (nh,), jnp.float32,
                           np.log(1e-3), np.log(1e-1))
    dt0 = jnp.exp(u)
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))
    return {
        "in_proj": common.dense_param(ks[0], (d, proj_out), dt),
        "conv_w": (jax.random.normal(ks[1], (s.conv_width, conv_ch), jnp.float32)
                   * (1.0 / np.sqrt(s.conv_width))).astype(dt),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "A_log": jnp.log(jax.random.uniform(ks[3], (nh,), jnp.float32, 1.0, 16.0)),
        "dt_bias": dt_bias,
        "D": jnp.ones((nh,), jnp.float32),
        "norm_scale": jnp.ones((di,), jnp.float32),
        "out_proj": common.dense_param(ks[4], (di, d), dt),
    }


def _split_proj(cfg, proj):
    s = cfg.ssm
    di, nh, _ = ssm_dims(cfg)
    gn = s.n_groups * s.d_state
    z, xs, Bm, Cm, dt_raw = jnp.split(
        proj, [di, 2 * di, 2 * di + gn, 2 * di + 2 * gn], axis=-1)
    return z, xs, Bm, Cm, dt_raw


def _causal_conv(cfg, p, xbc):
    """Depthwise causal conv over (B, S, C) channels."""
    s = cfg.ssm
    W = s.conv_width
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        pad, p["conv_w"][:, None, :].astype(xbc.dtype),
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NHC", "HIO", "NHC"),
        feature_group_count=xbc.shape[-1])
    return jax.nn.silu(out + p["conv_b"].astype(xbc.dtype))


def _gated_norm(p, y, z, eps=1e-5):
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return y * p["norm_scale"]


def ssd_forward(cfg, p, x, *, initial_state=None, return_state=False,
                use_kernels=False):
    """Full-sequence SSD. x: (B, S, d) -> (B, S, d).

    Scans over chunks of `cfg.ssm.chunk_size`; requires S % chunk == 0 or
    S <= chunk.
    """
    s = cfg.ssm
    di, nh, _ = ssm_dims(cfg)
    hpg = nh // s.n_groups
    B_, S, _ = x.shape
    Q = min(s.chunk_size, S)
    assert S % Q == 0, f"seq {S} not divisible by chunk {Q}"
    nc = S // Q

    proj = x @ p["in_proj"]
    z, xs, Bm, Cm, dt_raw = _split_proj(cfg, proj)
    xbc_raw = jnp.concatenate([xs, Bm, Cm], axis=-1)
    with jax.named_scope("ssm.conv"):
        # pre-conv window for decode (pad in case S < conv_width - 1)
        conv_tail = jnp.pad(
            xbc_raw, ((0, 0), (max(s.conv_width - 1 - S, 0), 0), (0, 0))
        )[:, -(s.conv_width - 1):]
        xbc = _causal_conv(cfg, p, xbc_raw)
    xs, Bm, Cm = jnp.split(xbc, [di, di + s.n_groups * s.d_state], axis=-1)

    xh = xs.reshape(B_, S, nh, s.head_dim)
    Bg = Bm.reshape(B_, S, s.n_groups, s.d_state)
    Cg = Cm.reshape(B_, S, s.n_groups, s.d_state)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,nh)
    A = -jnp.exp(p["A_log"])  # (nh,)
    dA = dt * A  # (B,S,nh), negative

    # chunked tensors: (nc, B, Q, ...)
    def chunked(t):
        return t.reshape(B_, nc, Q, *t.shape[2:]).transpose(1, 0, *range(2, t.ndim + 1))

    xc, Bc, Cc = chunked(xh), chunked(Bg), chunked(Cg)
    dtc, dAc = chunked(dt), chunked(dA)

    # expand groups -> heads upfront: (nc, B, Q, nh, N)
    Bc = jnp.repeat(Bc, hpg, axis=3).reshape(nc, B_, Q, nh, s.d_state)
    Cc = jnp.repeat(Cc, hpg, axis=3).reshape(nc, B_, Q, nh, s.d_state)
    h0 = (initial_state if initial_state is not None
          else jnp.zeros((B_, nh, s.head_dim, s.d_state), jnp.float32))

    with jax.named_scope("ssm.chunk_scan"):
        if use_kernels:
            from repro.kernels import ssd_scan
            final, yc = ssd_scan.ssd_chunk_scan(xc, Bc, Cc, dtc, dAc, h0)
            y = yc.transpose(1, 0, 2, 3, 4).reshape(B_, S, nh, s.head_dim)
        else:
            def body(h, xs_):
                x_i, B_i, C_i, dt_i, dA_i = xs_
                cum = jnp.cumsum(dA_i, axis=1)          # (B,Q,nh)
                total = cum[:, -1]                      # (B,nh)
                # intra-chunk dual (quadratic, attention-like) form
                cb = jnp.einsum("bihn,bjhn->bhij", C_i.astype(jnp.float32),
                                B_i.astype(jnp.float32))           # (B,nh,Q,Q)
                li = cum.transpose(0, 2, 1)[:, :, :, None]         # (B,nh,Q,1)
                lj = cum.transpose(0, 2, 1)[:, :, None, :]         # (B,nh,1,Q)
                # mask BEFORE exp: the i<j branch would overflow and poison
                # gradients through the where
                diff = jnp.where(jnp.tril(jnp.ones((Q, Q), bool)),
                                 li - lj, -1e30)
                decay = jnp.exp(diff)
                scores = cb * decay * dt_i.transpose(0, 2, 1)[:, :, None, :]
                y_intra = jnp.einsum("bhij,bjhp->bihp", scores,
                                     x_i.astype(jnp.float32))
                # carried-state contribution
                y_inter = jnp.einsum("bihn,bhpn->bihp",
                                     C_i.astype(jnp.float32)
                                     * jnp.exp(cum)[..., None], h)
                with jax.named_scope("ssm.state_update"):
                    w = dt_i * jnp.exp(total[:, None, :] - cum)    # (B,Q,nh)
                    dstate = jnp.einsum("bjhp,bjhn->bhpn",
                                        x_i.astype(jnp.float32) * w[..., None],
                                        B_i.astype(jnp.float32))
                    h_new = jnp.exp(total)[:, :, None, None] * h + dstate
                return h_new, y_intra + y_inter

            final, yc = jax.lax.scan(body, h0, (xc, Bc, Cc, dtc, dAc))
            y = yc.transpose(1, 0, 2, 3, 4).reshape(B_, S, nh, s.head_dim)

    y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = _gated_norm(p, y.reshape(B_, S, di), z)
    out = y.astype(x.dtype) @ p["out_proj"]
    if return_state:
        return out, (conv_tail, final)
    return out


def ssd_decode_step(cfg, p, x, conv_state, ssm_state):
    """One-token decode. x: (B,1,d); conv_state: (B, W-1, conv_ch);
    ssm_state: (B, nh, hd, N) f32. Returns (y, new_conv_state, new_ssm_state).
    """
    s = cfg.ssm
    di, nh, conv_ch = ssm_dims(cfg)
    B_ = x.shape[0]
    proj = x @ p["in_proj"]
    z, xs, Bm, Cm, dt_raw = _split_proj(cfg, proj)
    xbc = jnp.concatenate([xs, Bm, Cm], axis=-1)  # (B,1,C)
    with jax.named_scope("ssm.conv"):
        window = jnp.concatenate([conv_state, xbc], axis=1)  # (B,W,C)
        new_conv_state = window[:, 1:]
        conv_out = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32),
                              p["conv_w"].astype(jnp.float32)) + p["conv_b"].astype(jnp.float32)
        conv_out = jax.nn.silu(conv_out)[:, None, :].astype(x.dtype)
    xs, Bm, Cm = jnp.split(conv_out, [di, di + s.n_groups * s.d_state], axis=-1)

    xh = xs.reshape(B_, nh, s.head_dim).astype(jnp.float32)
    hpg = nh // s.n_groups
    Bh = jnp.repeat(Bm.reshape(B_, s.n_groups, s.d_state), hpg, axis=1)
    Ch = jnp.repeat(Cm.reshape(B_, s.n_groups, s.d_state), hpg, axis=1)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,nh)
    A = -jnp.exp(p["A_log"])
    with jax.named_scope("ssm.state_update"):
        a = jnp.exp(dt * A)  # (B,nh)
        dstate = jnp.einsum("bhp,bhn->bhpn", xh * dt[..., None],
                            Bh.astype(jnp.float32))
        new_state = a[:, :, None, None] * ssm_state + dstate
        y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch.astype(jnp.float32))
    y = y + p["D"][None, :, None] * xh
    y = _gated_norm(p, y.reshape(B_, 1, di), z)
    return y.astype(x.dtype) @ p["out_proj"], new_conv_state, new_state
