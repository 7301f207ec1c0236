"""Dense decoder-only transformer: pre-norm attention (GQA, rotary) and a
SwiGLU feed-forward per layer, tied or untied embeddings.

Everything the benchmark needs to know about this family lives here:

- ``arch_config``: the program's ``ArchConfig`` for a configuration file;
- ``tiny``: a tiny configuration of the same kind, for the CPU tests;
- ``make_weights``: seeded weights, made on the device in one program, in a
  layout of the benchmark's own, and ``program_params``, the same arrays
  re-nested the way ``PodEngine`` takes them (no copy);
- ``logit_gaps``: the plain float32 reference forward pass, and its
  lower-precision control, reduced to the check's numbers;
- the operation and byte counts of a step (``decode_cost``,
  ``request_flops``, ``weight_bytes``).

The reference follows the published description of the architecture
(Qwen2 / OLMo: rotate-half rotary embeddings, softmax attention scaled by
``1/sqrt(head_dim)``, SiLU-gated feed-forward), imports nothing of the
program and computes in float32 at highest matmul precision.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of one configuration file, under the benchmark's names."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str            # "rmsnorm" | "nonparametric_ln"
    norm_eps: float
    rope_theta: float
    qkv_bias: bool
    tied: bool

    @classmethod
    def of(cls, c: dict) -> "Shape":
        """From a configuration file: the published ``config.json`` keys
        at the top level, with ``norm`` and ``qkv_bias`` beside them."""
        heads = c["num_attention_heads"]
        return cls(
            layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            heads=heads, kv_heads=c.get("num_key_value_heads", heads),
            head_dim=c.get("head_dim", c["hidden_size"] // heads),
            d_ff=c["intermediate_size"], vocab=c["vocab_size"],
            norm=c["norm"],
            norm_eps=float(c.get("rms_norm_eps", c.get("layer_norm_eps"))),
            rope_theta=float(c["rope_theta"]), qkv_bias=bool(c["qkv_bias"]),
            tied=bool(c["tie_word_embeddings"]))


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for this file, as published; the
    file's norm eps goes in where ``ArchConfig`` has a ``norm_eps``."""
    from repro.configs import base
    s = Shape.of(conf)
    extra = {}
    if "norm_eps" in {f.name for f in dataclasses.fields(base.ArchConfig)}:
        extra["norm_eps"] = s.norm_eps
    return base.ArchConfig(
        name=conf["name"], family="dense", source=conf["source"],
        num_layers=s.layers, d_model=s.d_model, num_heads=s.heads,
        num_kv_heads=s.kv_heads, head_dim=s.head_dim, d_ff=s.d_ff,
        vocab_size=s.vocab, qkv_bias=s.qkv_bias, norm=s.norm, act="silu",
        rope_theta=s.rope_theta, tie_embeddings=s.tied, dtype="bfloat16",
        **extra)


TINY = {"name": "tiny", "source": "test", "family": "dense_decoder",
        "norm": "rmsnorm", "qkv_bias": True, "hidden_size": 128,
        "intermediate_size": 256, "num_hidden_layers": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": True}


def tiny(conf: dict) -> dict:
    """A tiny configuration with ``conf``'s norm and biases."""
    return dict(TINY, norm=conf["norm"], qkv_bias=conf["qkv_bias"])


# ------------------------------------------------------------ weights
def _matrix_shapes(s: Shape) -> Dict[str, tuple]:
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    return {"wq": (s.d_model, q), "wk": (s.d_model, kv),
            "wv": (s.d_model, kv), "wo": (q, s.d_model),
            "w_gate": (s.d_model, s.d_ff), "w_up": (s.d_model, s.d_ff),
            "w_down": (s.d_ff, s.d_model)}


def make_weights(s: Shape, key) -> dict:
    """Seeded weights (call under ``jax.jit`` with ``s`` static).

    Matrices are bfloat16, normal with std ``1/sqrt(fan_in)``; the
    embedding is normal with std 0.02; norm scales are float32 around 1
    and biases bfloat16 around 0, both random so that the check sees them
    applied. Per-layer leaves are stacked on a leading layer axis."""
    L, bf = s.layers, jnp.bfloat16
    shapes = _matrix_shapes(s)
    keys = iter(jax.random.split(key, 16))
    layers = {name: (jax.random.normal(next(keys), (L,) + shp, jnp.float32)
                     * shp[0] ** -0.5).astype(bf)
              for name, shp in shapes.items()}
    if s.qkv_bias:
        for b, w in (("bq", "wq"), ("bk", "wk"), ("bv", "wv")):
            layers[b] = (0.1 * jax.random.normal(
                next(keys), (L, shapes[w][1]), jnp.float32)).astype(bf)
    if s.norm == "rmsnorm":
        for n in ("ln1", "ln2"):
            layers[n] = 1.0 + 0.1 * jax.random.normal(
                next(keys), (L, s.d_model), jnp.float32)
    w = {"embed": (0.02 * jax.random.normal(
        next(keys), (s.vocab, s.d_model), jnp.float32)).astype(bf),
        "layers": layers}
    if s.norm == "rmsnorm":
        w["ln_f"] = 1.0 + 0.1 * jax.random.normal(
            next(keys), (s.d_model,), jnp.float32)
    if not s.tied:
        w["unembed"] = (jax.random.normal(
            next(keys), (s.d_model, s.vocab), jnp.float32)
            * s.d_model ** -0.5).astype(bf)
    return w


def program_params(s: Shape, w: dict) -> dict:
    """``w`` re-nested as the program's dense stack: no prefix, one
    scanned period of one block (the same arrays, not copies)."""
    lw = w["layers"]

    def norm(name):
        return {"scale": lw[name]} if s.norm == "rmsnorm" else {}

    attn = {k: lw[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in lw}
    block = {"ln1": norm("ln1"), "attn": attn, "ln2": norm("ln2"),
             "ffn": {k: lw[k] for k in ("w_gate", "w_up", "w_down")}}
    p = {"embed": w["embed"], "stack": {"prefix": [], "periods": (block,)},
         "ln_f": {"scale": w["ln_f"]} if s.norm == "rmsnorm" else {}}
    if not s.tied:
        p["unembed"] = w["unembed"]
    return p


# ------------------------------------------------------------ reference
def _norm(s: Shape, x, scale=None):
    if s.norm == "rmsnorm":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + s.norm_eps)
        return x * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + s.norm_eps)


def _rope(x, pos, theta):
    """Rotate-half rotary embedding. x: (T, heads, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv          # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _fp8(w):
    """``w`` rounded through float8_e4m3fn with one scale per matrix."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / F8_MAX
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def forward(s: Shape, w: dict, tokens, low_precision: bool = False):
    """Float32 logits ``(T, vocab)`` of one sequence ``tokens (T,)``.

    With ``low_precision`` every weight matrix is first rounded to
    float8 (one scale per matrix): the control, one precision step below
    the bfloat16 that the configurations state."""
    f32 = jnp.float32
    cast = _fp8 if low_precision else (lambda a: a.astype(f32))
    T = tokens.shape[0]
    pos = jnp.arange(T)
    G = s.heads // s.kv_heads
    causal = pos[None, :] <= pos[:, None]

    def mm(x, a):
        return jnp.einsum("td,df->tf", x, cast(a), precision=HIGHEST)

    def layer(x, lw):
        h = _norm(s, x, lw.get("ln1"))
        q, k, v = mm(h, lw["wq"]), mm(h, lw["wk"]), mm(h, lw["wv"])
        if s.qkv_bias:
            q = q + lw["bq"].astype(f32)
            k = k + lw["bk"].astype(f32)
            v = v + lw["bv"].astype(f32)
        q = _rope(q.reshape(T, s.heads, s.head_dim), pos, s.rope_theta)
        k = _rope(k.reshape(T, s.kv_heads, s.head_dim), pos, s.rope_theta)
        v = v.reshape(T, s.kv_heads, s.head_dim)
        q = q.reshape(T, s.kv_heads, G, s.head_dim)
        sc = jnp.einsum("tkgd,ukd->kgtu", q, k, precision=HIGHEST)
        sc = jnp.where(causal, sc * s.head_dim ** -0.5, -jnp.inf)
        o = jnp.einsum("kgtu,ukd->tkgd", jax.nn.softmax(sc, -1), v,
                       precision=HIGHEST)
        x = x + mm(o.reshape(T, s.heads * s.head_dim), lw["wo"])
        h = _norm(s, x, lw.get("ln2"))
        x = x + mm(jax.nn.silu(mm(h, lw["w_gate"])) * mm(h, lw["w_up"]),
                   lw["w_down"])
        return x, None

    x = w["embed"][tokens].astype(f32)
    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _norm(s, x, w.get("ln_f"))
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
    """For one sequence ``tokens (T,)``: at each position ``t < T-1`` the
    reference's best logit minus its logit of ``tokens[t+1]`` (the served
    token's gap), and the same gap for the token that the float8 control
    puts first. Both ``(T-1,)`` float32; a sound program's gap is 0 up to
    rounding. Call under ``jax.jit`` with ``s`` static."""
    ref = forward(s, w, tokens)[:-1]
    best = ref.max(-1)
    served = jnp.take_along_axis(ref, tokens[1:, None], -1)[:, 0]
    ctrl_tok = forward(s, w, tokens, low_precision=True)[:-1].argmax(-1)
    ctrl = jnp.take_along_axis(ref, ctrl_tok[:, None], -1)[:, 0]
    return best - served, best - ctrl


# ------------------------------------------------------------ counts
def _layer_matmul_params(s: Shape) -> int:
    return sum(a * b for a, b in _matrix_shapes(s).values())


def param_count(s: Shape) -> int:
    """Every parameter: matrices, embedding(s), biases, norm scales."""
    per_layer = _layer_matmul_params(s)
    if s.qkv_bias:
        per_layer += (s.heads + 2 * s.kv_heads) * s.head_dim
    if s.norm == "rmsnorm":
        per_layer += 2 * s.d_model
    n = s.layers * per_layer + s.vocab * s.d_model
    if not s.tied:
        n += s.vocab * s.d_model
    if s.norm == "rmsnorm":
        n += s.d_model
    return n


def weight_bytes(s: Shape) -> int:
    """Bytes of the weights as served: bfloat16 matrices, embeddings and
    biases; float32 norm scales."""
    n_norm = (2 * s.layers + 1) * s.d_model if s.norm == "rmsnorm" else 0
    return 2 * (param_count(s) - n_norm) + 4 * n_norm


def _kv_bytes_per_token(s: Shape) -> int:
    return 2 * s.layers * s.kv_heads * s.head_dim * 2   # K and V, bf16


def _attn_flops(s: Shape, n_keys) -> float:
    """QK^T and PV of one query over ``n_keys`` keys, all layers."""
    return 4.0 * s.layers * s.heads * s.head_dim * n_keys


def decode_cost(s: Shape, batch: int, pos: int) -> tuple:
    """(FLOPs, bytes) one decode step needs at ``batch`` rows whose new
    token sits at position ``pos``: every weight read once, each row's
    K/V over its ``pos + 1`` valid positions read and its new slot
    written; matmuls over all weights, the LM head included. Slots past
    the position and a copy of the cache are not needed, so not counted."""
    flops = batch * (2.0 * (s.layers * _layer_matmul_params(s)
                            + s.vocab * s.d_model)
                     + _attn_flops(s, pos + 1))
    nbytes = weight_bytes(s) + batch * (pos + 2) * _kv_bytes_per_token(s)
    return flops, float(nbytes)


def request_flops(s: Shape, prompt_len: int, n_out: int) -> float:
    """Model FLOPs of one request's useful work: its prompt and its own
    ``n_out`` served tokens (the last one needs no forward pass), with
    the LM head at the ``n_out`` positions that produced a token."""
    n = prompt_len + n_out - 1
    return (2.0 * s.layers * _layer_matmul_params(s) * n
            + 2.0 * s.vocab * s.d_model * n_out
            + _attn_flops(s, n * (n + 1) / 2))
