"""Mamba-2: attention-free decoder of SSD blocks (Dao & Gu, "Transformers
are SSMs", arXiv:2405.21060), as ``mamba_ssm``'s ``MambaLMHeadModel``
builds it with ``d_intermediate`` 0: per layer a pre-norm Mamba2 mixer
and no MLP, then a final RMSNorm and a tied LM head.

Everything the benchmark needs to know about this family lives here
(``bench/families/dense_decoder.py`` says what each name is for):
``Shape``, ``arch_config``, ``tiny``, ``make_weights``,
``program_params``, ``served_gaps``, ``logit_gaps``, ``decode_cost``,
``prefill_cost``, ``request_flops``, ``weight_bytes`` and
``state_bytes``.

The reference (``forward``) imports nothing of the program and computes
in float32 at highest matmul precision. Per layer it follows
``mamba_ssm.modules.mamba2.Mamba2`` with its defaults: one input
projection split into ``z``, ``xBC`` and ``dt``; a causal depthwise
convolution of width ``d_conv`` with bias over ``xBC``, written out as
a sum of shifted copies, then SiLU; ``dt = softplus(dt + dt_bias)``
(``dt_limit`` (0, inf): no clamp); ``A = -exp(A_log)``; the SSM as the
token-by-token recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = C_t h_t + D x_t

in a ``lax.scan`` over tokens, with no chunking, no cache and no batch;
``RMSNormGated`` with ``norm_before_gate`` false,
``norm(y * silu(z)) * weight`` over groups of ``d_inner / ngroups``;
an output projection without bias. The residual stream is float32 as
``residual_in_fp32`` says (the whole reference is). Departures: weights
are random from the seed, not the checkpoint; ``fused_add_norm`` is a
kernel choice with the same mathematics; the padded vocabulary rows are
ordinary rows (the checkpoint never ranks them first, random weights
may).

The control is the same pass with its matrices (input and output
projections, LM head) rounded to float8 e4m3, one scale per matrix, as
``dense_decoder._fp8`` does.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from bench.families.dense_decoder import _fp8

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of one configuration file, under the benchmark's names."""
    layers: int
    d_model: int
    expand: int
    head_dim: int
    d_state: int
    groups: int
    conv: int             # depthwise conv width, d_conv
    chunk: int            # the program's SSD chunk
    vocab: int
    norm_eps: float
    tied: bool

    @classmethod
    def of(cls, c: dict) -> "Shape":
        """From a configuration file: the published ``config.json`` keys
        and the Mamba2 module's settings at the top level."""
        return cls(
            layers=c["n_layer"], d_model=c["d_model"], expand=c["expand"],
            head_dim=c["headdim"], d_state=c["d_state"],
            groups=c["ngroups"], conv=c["d_conv"], chunk=c["chunk_size"],
            vocab=c["vocab_size"], norm_eps=float(c["norm_epsilon"]),
            tied=bool(c["tie_embeddings"]))

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def heads(self) -> int:
        return self.d_inner // self.head_dim

    @property
    def conv_ch(self) -> int:
        """Channels of ``xBC``, the convolved part of the projection."""
        return self.d_inner + 2 * self.groups * self.d_state

    @property
    def proj_out(self) -> int:
        """Columns of the input projection: ``z``, ``xBC``, ``dt``."""
        return self.d_inner + self.conv_ch + self.heads


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for this file, as published; the
    file's norm eps goes in where ``ArchConfig`` has a ``norm_eps``."""
    from repro.configs import base
    s = Shape.of(conf)
    extra = {}
    if "norm_eps" in {f.name for f in dataclasses.fields(base.ArchConfig)}:
        extra["norm_eps"] = s.norm_eps
    return base.ArchConfig(
        name=conf["name"], family="ssm", source=conf["source"],
        num_layers=s.layers, d_model=s.d_model, num_heads=0,
        num_kv_heads=0, head_dim=s.head_dim, d_ff=0, vocab_size=s.vocab,
        ssm=base.SSMConfig(d_state=s.d_state, expand=s.expand,
                           head_dim=s.head_dim, n_groups=s.groups,
                           conv_width=s.conv, chunk_size=s.chunk),
        norm="rmsnorm", tie_embeddings=s.tied, dtype="bfloat16", **extra)


TINY = {"name": "tiny", "source": "test", "family": "mamba2",
        "d_model": 128, "n_layer": 2, "vocab_size": 512, "d_state": 16,
        "d_conv": 4, "expand": 2, "headdim": 32, "ngroups": 1,
        "chunk_size": 4, "norm_epsilon": 1e-5, "tie_embeddings": True}


def tiny(conf: dict) -> dict:
    """A tiny configuration of the same kind: 2 layers and a chunk of 4,
    so that the CPU tests' 16-token prompts span four chunks and the
    chunk carry is exercised."""
    return dict(TINY, ngroups=conf["ngroups"])


# ------------------------------------------------------------ weights
def make_weights(s: Shape, key) -> dict:
    """Seeded weights (call under ``jax.jit`` with ``s`` static).

    The projections are bfloat16, normal with std ``1/sqrt(fan_in)``;
    the embedding is normal with std 0.02; the conv weight and bias are
    bfloat16, uniform in ``±1/sqrt(d_conv)`` (torch's default for a
    depthwise conv). ``A_log`` is ``log U[1, 16]`` and ``dt_bias`` the
    inverse softplus of ``dt``, log-uniform in [1e-3, 1e-1], both as
    ``Mamba2`` initialises them; ``D`` and the norm scales are float32
    around 1, random so that the check sees them applied. Per-layer
    leaves are stacked on a leading layer axis."""
    L, bf, f32 = s.layers, jnp.bfloat16, jnp.float32
    k = iter(jax.random.split(key, 16))
    bound = s.conv ** -0.5

    def normal(shape, std, dtype=bf):
        return (std * jax.random.normal(next(k), shape, f32)).astype(dtype)

    def around_one(shape):
        return 1.0 + normal(shape, 0.1, f32)

    log_dt = jax.random.uniform(next(k), (L, s.heads), f32,
                                jnp.log(1e-3), jnp.log(1e-1))
    dt = jnp.maximum(jnp.exp(log_dt), 1e-4)
    layers = {
        "norm": around_one((L, s.d_model)),
        "in_proj": normal((L, s.d_model, s.proj_out), s.d_model ** -0.5),
        "conv_w": jax.random.uniform(next(k), (L, s.conv, s.conv_ch), f32,
                                     -bound, bound).astype(bf),
        "conv_b": jax.random.uniform(next(k), (L, s.conv_ch), f32,
                                     -bound, bound).astype(bf),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(next(k), (L, s.heads), f32,
                                            1.0, 16.0)),
        "D": around_one((L, s.heads)),
        "gate_norm": around_one((L, s.d_inner)),
        "out_proj": normal((L, s.d_inner, s.d_model), s.d_inner ** -0.5),
    }
    w = {"embed": normal((s.vocab, s.d_model), 0.02), "layers": layers,
         "norm_f": around_one((s.d_model,))}
    if not s.tied:
        w["unembed"] = normal((s.d_model, s.vocab), s.d_model ** -0.5)
    return w


def program_params(s: Shape, w: dict) -> dict:
    """``w`` re-nested as the program's SSM stack: no prefix, one scanned
    period of one block (the same arrays, not copies)."""
    lw = w["layers"]
    mixer = {"in_proj": lw["in_proj"], "conv_w": lw["conv_w"],
             "conv_b": lw["conv_b"], "A_log": lw["A_log"],
             "dt_bias": lw["dt_bias"], "D": lw["D"],
             "norm_scale": lw["gate_norm"], "out_proj": lw["out_proj"]}
    p = {"embed": w["embed"],
         "stack": {"prefix": [],
                   "periods": ({"ln1": {"scale": lw["norm"]},
                                "ssm": mixer},)},
         "ln_f": {"scale": w["norm_f"]}}
    if not s.tied:
        p["unembed"] = w["unembed"]
    return p


# ------------------------------------------------------------ reference
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _recurrence(s: Shape, xh, Bt, Ct, dt, A):
    """The SSM over one sequence, one token at a time. ``xh (T, heads,
    head_dim)``, ``Bt``/``Ct (T, groups, d_state)``, ``dt (T, heads)``;
    returns ``y (T, heads, head_dim)`` without the ``D`` skip."""
    group = jnp.arange(s.heads) // (s.heads // s.groups)

    def step(h, inp):                     # h: (heads, head_dim, d_state)
        x_t, B_t, C_t, dt_t = inp
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[group][:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, C_t[group], precision=HIGHEST)

    h0 = jnp.zeros((s.heads, s.head_dim, s.d_state), jnp.float32)
    return jax.lax.scan(step, h0, (xh, Bt, Ct, dt))[1]


def forward(s: Shape, w: dict, tokens, low_precision: bool = False):
    """Float32 logits ``(T, vocab)`` of one sequence ``tokens (T,)``.

    With ``low_precision`` every weight matrix is first rounded to
    float8 (one scale per matrix): the control, one precision step below
    the bfloat16 that the configuration states."""
    f32 = jnp.float32
    cast = _fp8 if low_precision else (lambda a: a.astype(f32))
    T = tokens.shape[0]
    di, gn = s.d_inner, s.groups * s.d_state

    def mm(x, a):
        return jnp.einsum("td,df->tf", x, cast(a), precision=HIGHEST)

    def layer(x, lw):
        zxbcdt = mm(_rms(x, lw["norm"], s.norm_eps), lw["in_proj"])
        z, xbc, dt = jnp.split(zxbcdt, [di, di + s.conv_ch], axis=-1)
        # causal depthwise conv: output t sees inputs t-conv+1 .. t
        cw = lw["conv_w"].astype(f32)
        padded = jnp.pad(xbc, ((s.conv - 1, 0), (0, 0)))
        xbc = jax.nn.silu(sum(padded[i:i + T] * cw[i] for i in range(s.conv))
                          + lw["conv_b"].astype(f32))
        xs, Bt, Ct = jnp.split(xbc, [di, di + gn], axis=-1)
        dt = jax.nn.softplus(dt + lw["dt_bias"])
        xh = xs.reshape(T, s.heads, s.head_dim)
        y = _recurrence(s, xh, Bt.reshape(T, s.groups, s.d_state),
                        Ct.reshape(T, s.groups, s.d_state), dt,
                        -jnp.exp(lw["A_log"]))
        y = (y + lw["D"][:, None] * xh).reshape(T, di) * jax.nn.silu(z)
        y = _rms(y.reshape(T, s.groups, di // s.groups), 1.0, s.norm_eps)
        y = y.reshape(T, di) * lw["gate_norm"]
        return x + mm(y, lw["out_proj"]), None

    x = w["embed"][tokens].astype(f32)
    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _rms(x, w["norm_f"], s.norm_eps)
    out = w["embed"].T if s.tied else w["unembed"]
    return jnp.einsum("td,dv->tv", x, cast(out), precision=HIGHEST)


def served_gaps(s: Shape, w: dict, tokens):
    """At each position ``t < T-1`` of one sequence ``tokens (T,)``, the
    reference's best logit minus its logit of ``tokens[t+1]``, the served
    token: 0 for a sound program, up to rounding. Call under ``jax.jit``
    with ``s`` static."""
    ref = forward(s, w, tokens)[:-1]
    return ref.max(-1) - jnp.take_along_axis(ref, tokens[1:, None], -1)[:, 0]


def logit_gaps(s: Shape, w: dict, tokens):
    """The served token's gap (``served_gaps``) and the same gap for the
    token that the float8 control puts first, both ``(T-1,)`` float32.
    Call under ``jax.jit`` with ``s`` static."""
    ref = forward(s, w, tokens)[:-1]
    best = ref.max(-1)
    served = jnp.take_along_axis(ref, tokens[1:, None], -1)[:, 0]
    ctrl_tok = forward(s, w, tokens, low_precision=True)[:-1].argmax(-1)
    ctrl = jnp.take_along_axis(ref, ctrl_tok[:, None], -1)[:, 0]
    return best - served, best - ctrl


# ------------------------------------------------------------ counts
def _layer_shapes(s: Shape) -> Dict[str, int]:
    """Parameters of one layer, by leaf."""
    return {"norm": s.d_model, "in_proj": s.d_model * s.proj_out,
            "conv_w": s.conv * s.conv_ch, "conv_b": s.conv_ch,
            "dt_bias": s.heads, "A_log": s.heads, "D": s.heads,
            "gate_norm": s.d_inner, "out_proj": s.d_inner * s.d_model}


_F32_LEAVES = ("norm", "dt_bias", "A_log", "D", "gate_norm")


def param_count(s: Shape) -> int:
    """Every parameter: projections, conv, SSM parameters, norm scales,
    embedding(s)."""
    n = s.layers * sum(_layer_shapes(s).values()) + s.vocab * s.d_model \
        + s.d_model
    return n + (0 if s.tied else s.vocab * s.d_model)


def weight_bytes(s: Shape) -> int:
    """Bytes of the weights as served: bfloat16 projections, conv and
    embeddings; float32 ``A_log``, ``dt_bias``, ``D`` and norm scales."""
    per = _layer_shapes(s)
    n_f32 = s.layers * sum(per[k] for k in _F32_LEAVES) + s.d_model
    return 2 * (param_count(s) - n_f32) + 4 * n_f32


def _state_elems(s: Shape) -> tuple:
    """Elements of one row's (SSM state, conv window), all layers."""
    return (s.layers * s.heads * s.head_dim * s.d_state,
            s.layers * (s.conv - 1) * s.conv_ch)


def state_bytes(s: Shape, batch: int = 1) -> int:
    """Bytes of recurrent state the program holds for ``batch`` rows:
    the SSM state in float32 and the conv window in bfloat16."""
    ssm, conv = _state_elems(s)
    return batch * (4 * ssm + 2 * conv)


def _needed_state_bytes(s: Shape) -> int:
    """One row's state at bfloat16, as a bfloat16 deployment of
    ``mamba_ssm`` allocates it."""
    return 2 * sum(_state_elems(s))


def _token_flops(s: Shape) -> float:
    """One token through every layer by the recurrence, LM head aside:
    the projections, the conv, the state update (decay and outer product,
    3 FLOPs an element) and its read-out (2 FLOPs an element)."""
    proj = 2.0 * (s.d_model * s.proj_out + s.d_inner * s.d_model)
    return s.layers * (proj + 2.0 * s.conv * s.conv_ch
                       + 5.0 * s.heads * s.head_dim * s.d_state)


def decode_cost(s: Shape, batch: int, pos: int) -> tuple:
    """(FLOPs, bytes) one decode step needs at ``batch`` rows (``pos``
    does not matter: the state has one size): every weight read once,
    each row's SSM and conv state read and written once at bfloat16;
    matmuls over all weights, the LM head included. The undonated copy
    of the state is not needed, so not counted."""
    flops = batch * (_token_flops(s) + 2.0 * s.vocab * s.d_model)
    nbytes = weight_bytes(s) + batch * 2 * _needed_state_bytes(s)
    return flops, float(nbytes)


def prefill_cost(s: Shape, batch: int, prompt_len: int) -> tuple:
    """(FLOPs, bytes) one prefill of ``batch`` prompts of ``prompt_len``
    tokens needs by the chunked dual form (chunks of ``chunk``): the
    projections and the conv at every token; per chunk, ``C B^T`` once a
    group and the decayed scores times ``x`` once a head, both over the
    causal triangle; the carried state read out in every chunk after the
    first (the first starts from zero) and the state update of every
    chunk; the LM head at the last position. Bytes: every weight read
    once and each row's final state written, at bfloat16."""
    Q = min(s.chunk, prompt_len)
    n_chunks = prompt_len // Q
    tri = n_chunks * Q * (Q + 1) / 2
    hpn = s.heads * s.head_dim * s.d_state
    layer = (2.0 * prompt_len * (s.d_model * s.proj_out
                                 + s.d_inner * s.d_model)
             + 2.0 * prompt_len * s.conv * s.conv_ch
             + 2.0 * tri * s.groups * s.d_state
             + 2.0 * tri * s.heads * s.head_dim
             + 2.0 * (prompt_len - Q) * hpn
             + 2.0 * prompt_len * hpn)
    flops = batch * (s.layers * layer + 2.0 * s.vocab * s.d_model)
    nbytes = weight_bytes(s) + batch * _needed_state_bytes(s)
    return flops, float(nbytes)


def request_flops(s: Shape, prompt_len: int, n_out: int) -> float:
    """Model FLOPs of one request's useful work by the recurrence: its
    prompt and its own ``n_out`` served tokens (the last one needs no
    forward pass) through every layer, with the LM head at the ``n_out``
    positions that produced a token."""
    n = prompt_len + n_out - 1
    return _token_flops(s) * n + 2.0 * s.vocab * s.d_model * n_out
