"""The Mamba-2 family of the chip benchmark (``bench/families/mamba2.py``):
its float32 recurrence reference against the program's chunked SSD scan
and one-token decode, its float8 control, and its counts.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench_harness
"""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.families import mamba2 as fam  # noqa: E402

TINY = fam.tiny({"ngroups": 1})
PROMPT, STEPS, ROWS = 16, 8, 2
# Largest logit error of the bfloat16 program against the float32
# reference at the tiny size (logits of magnitude ~1): 0.0076 over seeds
# 0-3; the float8 control's is 0.056-0.079. bfloat16 keeps 8 bits of
# mantissa and e4m3 4, so the limit sits ~2.6x above the one and ~2.8x
# below the other.
LOGIT_TOL = 2e-2


@pytest.fixture(scope="module")
def tiny():
    s = fam.Shape.of(TINY)
    w = jax.jit(fam.make_weights, static_argnums=0)(s, jax.random.PRNGKey(0))
    return s, w


@pytest.fixture(scope="module")
def served(tiny):
    """The program's logits for a prompt and ``STEPS`` decode steps after
    it, through its cache, with the tokens they were fed: ``(ROWS,
    STEPS + 1, vocab)`` float32 and ``(ROWS, PROMPT + STEPS)``."""
    from repro.models import CallOpts
    from repro.training import steps
    s, w = tiny
    cfg = fam.arch_config(TINY)
    p = fam.program_params(s, w)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, s.vocab, (ROWS, PROMPT + STEPS)).astype(np.int32)
    logits, cache = jax.jit(steps.make_prefill_step(cfg, 32, CallOpts()))(
        p, {"tokens": jnp.asarray(toks[:, :PROMPT])})
    decode = jax.jit(steps.make_decode_step(cfg, CallOpts()))
    out = [logits[:, -1]]
    for i in range(STEPS):
        logits, cache = decode(p, jnp.asarray(toks[:, PROMPT + i:][:, :1]),
                               jnp.asarray(PROMPT + i, jnp.int32), cache)
        out.append(logits[:, -1])
    return np.asarray(jnp.stack(out, 1), np.float32), toks


def _reference(tiny, toks, low_precision=False):
    """The reference's logits at the positions the program served."""
    s, w = tiny
    fwd = jax.jit(fam.forward, static_argnums=(0, 3))
    return np.stack([np.asarray(fwd(s, w, jnp.asarray(t), low_precision))
                     [PROMPT - 1:] for t in toks])


def test_tiny_prompt_spans_several_chunks():
    assert PROMPT // TINY["chunk_size"] == 4
    assert TINY["n_layer"] == 2


def test_program_agrees_with_the_recurrence(tiny, served):
    """Prefill by the chunked dual form, then 8 decode steps through the
    conv window and SSM state, against the token-by-token recurrence."""
    prog, toks = served
    ref = _reference(tiny, toks)
    assert prog.shape == ref.shape == (ROWS, STEPS + 1, TINY["vocab_size"])
    assert np.abs(ref).max() > 0.5
    assert np.abs(prog - ref).max() < LOGIT_TOL


def test_float32_program_is_the_recurrence(tiny):
    """The same weights with the program computing in float32: the
    chunked dual form and the cached decode step are the recurrence's
    mathematics, so they agree to float32 rounding (the bfloat16 program
    differs by its activations' rounding alone)."""
    from repro.models import CallOpts
    from repro.training import steps
    s, w = tiny
    cfg = dataclasses.replace(fam.arch_config(TINY), dtype="float32")
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     fam.program_params(s, w))
    toks = np.random.default_rng(1).integers(
        0, s.vocab, (1, PROMPT + 1)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, cache = jax.jit(steps.make_prefill_step(
            cfg, 32, CallOpts()))(p, {"tokens": jnp.asarray(toks[:, :-1])})
        nxt, _ = jax.jit(steps.make_decode_step(cfg, CallOpts()))(
            p, jnp.asarray(toks[:, -1:]), jnp.asarray(PROMPT, jnp.int32),
            cache)
    prog = np.concatenate([np.asarray(logits[0]), np.asarray(nxt[0])])
    ref = _reference(tiny, toks)[0]
    assert np.abs(prog - ref).max() < 1e-4


def test_float8_control_fails_the_same_comparison(tiny, served):
    _, toks = served
    ref = _reference(tiny, toks)
    ctrl = _reference(tiny, toks, low_precision=True)
    assert np.abs(ctrl - ref).max() > LOGIT_TOL


# ------------------------------------------------------------ counts
# The tiny configuration by hand: d_inner 256, 8 heads of 32, d_state 16,
# conv channels 256 + 2*16 = 288, input projection 256 + 288 + 8 = 552.
TINY_LAYER = (128 + 128 * 552 + 4 * 288 + 288 + 3 * 8 + 256
              + 256 * 128)                        # 105,272
TINY_PARAMS = 2 * TINY_LAYER + 512 * 128 + 128    # 276,208
TINY_F32 = 2 * (128 + 3 * 8 + 256) + 128          # norms, dt_bias, A_log, D
TINY_WEIGHT_BYTES = 2 * (TINY_PARAMS - TINY_F32) + 4 * TINY_F32
TINY_SSM, TINY_CONV = 2 * 8 * 32 * 16, 2 * 3 * 288  # elements a row
LM_HEAD = 2 * 512 * 128


def test_weight_and_state_bytes_by_hand(tiny):
    s, w = tiny
    assert TINY_WEIGHT_BYTES == 554_304
    assert fam.param_count(s) == TINY_PARAMS == sum(
        x.size for x in jax.tree.leaves(w))
    assert fam.weight_bytes(s) == TINY_WEIGHT_BYTES == sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(w))
    assert fam.state_bytes(s) == 4 * TINY_SSM + 2 * TINY_CONV == 36_224
    assert fam.state_bytes(s, 3) == 3 * 36_224


def test_decode_cost_by_hand(tiny):
    s, _ = tiny
    token = 2 * (2 * (128 * 552 + 256 * 128) + 2 * 4 * 288
                 + 5 * 8 * 32 * 16)
    for batch in (1, 3):
        flops, nbytes = fam.decode_cost(s, batch, pos=20)
        assert flops == batch * (token + LM_HEAD)
        # the state read and written once a step, at bfloat16
        assert nbytes == TINY_WEIGHT_BYTES + batch * 4 * (TINY_SSM
                                                          + TINY_CONV)


def test_prefill_cost_by_hand(tiny):
    s, _ = tiny
    # 16 tokens in 4 chunks of 4: causal triangle 4 * 10 = 40 pairs;
    # heads x head_dim x d_state = 4096
    layer = (2 * 16 * (128 * 552 + 256 * 128) + 2 * 16 * 4 * 288
             + 2 * 40 * 16          # C B^T, one group
             + 2 * 40 * 8 * 32      # decayed scores times x, every head
             + 2 * 12 * 4096        # carried state, chunks 2-4
             + 2 * 16 * 4096)       # state update, every chunk
    assert layer == 3_597_568
    flops, nbytes = fam.prefill_cost(s, 3, 16)
    assert flops == 3 * (2 * layer + LM_HEAD)
    assert nbytes == TINY_WEIGHT_BYTES + 3 * 2 * (TINY_SSM + TINY_CONV)


def test_published_counts():
    conf = json.loads((ROOT / "bench" / "configs" / "mamba2-2.7b.json")
                      .read_text())
    s = fam.Shape.of(conf)
    assert (s.layers, s.d_model, s.heads, s.head_dim, s.d_state, s.groups,
            s.vocab) == (64, 2560, 80, 64, 128, 1, 50288)
    assert fam.param_count(s) == 2_702_599_680
    assert fam.weight_bytes(s) == 5_406_218_240
    assert fam.state_bytes(s) == 167_772_160 + 2_064_384
    cfg = fam.arch_config(conf)
    assert (cfg.family, cfg.ssm.n_groups, cfg.ssm.chunk_size,
            cfg.vocab_size) == ("ssm", 1, 256, 50288)
