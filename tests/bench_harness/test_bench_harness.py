"""Tests of the chip benchmark that run on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench_harness

- the reduction from a trace to metrics, on a synthetic trace;
- the operation and byte counts, against hand counts;
- the weights' layout against the program's own parameters;
- a rehearsal of whole runs at a tiny size (the chip check skipped),
  including the lower-precision control and planted faults, which the
  output check has to catch;
- the CLI's refusal of a machine without a TPU;
- compile-only runs of each cell's largest programs for a described v5e,
  with their memory.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import cell as cell_mod  # noqa: E402
from bench import control, readers, spec  # noqa: E402
from bench import run as run_mod  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench import traffic  # noqa: E402
from bench.families import dense_decoder as dd  # noqa: E402

CELLS = ("olmo-1b.chat", "olmo-1b.offline-doc")
TINY = {"name": "tiny", "source": "test", "family": "dense_decoder",
        "norm": "rmsnorm", "qkv_bias": True, "hidden_size": 128,
        "intermediate_size": 256, "num_hidden_layers": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": True}
TINY_POD = {"batch": 4, "max_seq": 32, "quota": 1.0}
TINY_LEN = {"kind": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
            "max": 16}
# The tiny cells' limit, from their own CPU readings over seeds 1, 2 and
# 3,000,000,019 of both: sound runs read at most 2.1e-3, the float8
# control at least 3.4e-2.
TINY_LIMIT = 2e-2


def tiny_cell(name: str = CELLS[0], **check) -> spec.Cell:
    """The cell ``name`` at a tiny size, with its norm and biases."""
    cell = spec.load_cell(name)
    t = dict(cell.traffic, prompt_len=16, pod=TINY_POD, output_len=TINY_LEN)
    if t["arrivals"]["kind"] == "poisson":
        t["arrivals"] = {"kind": "poisson", "rate_per_s": 20.0}
    conf = dict(TINY, norm=cell.config["norm"],
                qkv_bias=cell.config["qkv_bias"])
    return dataclasses.replace(
        cell, config=conf, traffic=t,
        check={"check_requests": 8, "logit_gap_limit": TINY_LIMIT, **check})


def tiny_run(cell: spec.Cell, seed: int = 3_000_000_019, seconds=1.0,
             trace: int = 0) -> dict:
    args = run_mod.parse(["--workload", cell.name, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", str(trace)])
    return run_mod.run(args, cell=cell, require_chip=False,
                       t_start=time.monotonic())


@pytest.fixture(autouse=True)
def _cache_outside_checkout(tmp_path_factory, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path_factory.getbasetemp() / "jax_cache"))


# ------------------------------------------------------------ trace
def synthetic_trace() -> tr.Trace:
    """One window (0-100) with two pumps; the first runs a prefill and
    three decodes, the second a prefill and two decodes."""
    mods = [("jit_prefill_step", 10, 20), ("jit_decode_step", 22, 25),
            ("jit_decode_step", 26, 29), ("jit_decode_step", 31, 34),
            ("jit_prefill_step", 60, 70), ("jit_decode_step", 70, 73),
            ("jit_decode_step", 75, 78)]
    ops = [("fusion.1", a, b) for _, a, b in mods] + [("copy.2", 71, 72)]
    spans = [("bench.window", 0, 100), ("bench.route", 5, 9),
             ("bench.pump", 9, 40), ("bench.route", 50, 55),
             ("bench.pump", 58, 90)]
    return tr.Trace(sorted(mods, key=lambda e: e[1]),
                    sorted(ops, key=lambda e: e[1]), spans)


def test_trace_union_busy_and_idle():
    t = synthetic_trace()
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert t.window() == (0, 100)
    assert tr.busy_ns(t) == 10 + 3 + 3 + 3 + 10 + 3 + 3
    gaps = tr.idle_gaps(t, n=3)
    assert [g[1] for g in gaps] == [26e-9, 22e-9, 10e-9]
    assert [g[0] for g in gaps] == ["none", "pump", "route"]
    # own time: the copy nested in a decode's fusion is the copy's
    assert tr.top_ops(t) == [("jit_prefill_step/fusion.1", 20e-9),
                             ("jit_decode_step/fusion.1", 14e-9),
                             ("jit_decode_step/copy.2", 1e-9)]


def test_short_names():
    assert tr.short_name("jit_decode_step(4121056591767977019)") == \
        "jit_decode_step"
    assert tr.short_name("%while.2 = (s32[], bf16[3,1,2048]) while(%t)") \
        == "%while.2"


def test_trace_programs_grouped_by_pump():
    t = synthetic_trace()
    dec = tr.programs(t, "decode_step")
    assert len(dec) == 5 and len(tr.programs(t, "prefill_step")) == 2
    groups = tr.grouped_by_span(t, dec, "pump")
    assert [len(g) for g in groups] == [3, 2]
    assert [tr.gaps_between(g) for g in groups] == [[1, 2], [2]]
    assert tr.span_at(t, 30) == "pump" and tr.span_at(t, 45) == "none"


def _fake_run(batches, trace=None):
    cell = dataclasses.replace(tiny_cell())
    return cell_mod.Run(
        cell=cell, shape=dd.Shape.of(TINY),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        t_start=0.0, t_window=1.0, t_last_end=3.0,
        attempted=[r for b in batches for r in b.reqs], batches=batches,
        compiles_in_window=0, trace=trace, t_trace=0.0)


def _batch(sizes_out, start, end, prompt_len=16):
    reqs = []
    for m in sizes_out:
        req = type("R", (), {"completed_at": end, "output": None})()
        reqs.append(cell_mod.Req(traffic.Planned(0.0, np.zeros(prompt_len,
                                                               np.int32), m),
                                 due=start - 0.5, request=req,
                                 pump_start=start))
    return cell_mod.Batch(start, end, prompt_len, max(sizes_out), reqs)


def test_readers_on_synthetic_run():
    run = _fake_run([_batch([3, 1], 1.0, 2.0), _batch([2], 2.0, 3.0)],
                    synthetic_trace())
    assert readers.program_ms(run, "decode_step") == pytest.approx(3e-6)
    assert readers.decode_host_gap_ms(run) == pytest.approx(5 / 3 * 1e-6)
    assert readers.slot_use(run) == pytest.approx(100 * 6 / (2 * 3 + 2))
    assert readers.output_tokens_per_s(run) == pytest.approx(6 / 2.0)
    assert readers.idle_share(run) == pytest.approx(100 * (1 - 35 / 100))
    assert readers.percentile(readers.queue_waits_ms(run), 50) == \
        pytest.approx(500.0)
    s = run.shape
    least = sum(max(f / 197e12, n / 819e9) for f, n in
                [dd.decode_cost(s, 2, 16 + i) for i in range(3)]
                + [dd.decode_cost(s, 1, 16 + i) for i in range(2)])
    assert readers.decode_roofline(run) == pytest.approx(
        100 * least / 15e-9)
    # a batch the trace does not hold: the counts disagree, no reading
    more = _fake_run(run.batches + [_batch([1], 3.0, 4.0)], run.trace)
    assert readers.decode_roofline(more) is None


def test_readers_drop_a_drain_program_inside_the_window():
    """A drain batch's prefill and decode that start just inside the
    window are not the traced batches' and are not read."""
    t = synthetic_trace()
    leak = [("jit_prefill_step", 91, 99), ("jit_decode_step", 99, 100)]
    t = tr.Trace(sorted(t.modules + leak, key=lambda e: e[1]),
                 sorted(t.ops + [("fusion.1", a, b) for _, a, b in leak],
                        key=lambda e: e[1]), t.spans)
    run = _fake_run([_batch([3, 1], 1.0, 2.0), _batch([2], 2.0, 3.0)], t)
    assert len(tr.programs(t, "prefill_step")) == 3
    assert readers.program_ms(run, "prefill_step") == pytest.approx(10e-6)
    assert readers.program_ms(run, "decode_step") == pytest.approx(3e-6)
    assert readers.decode_host_gap_ms(run) == pytest.approx(5 / 3 * 1e-6)
    clean = _fake_run(run.batches, synthetic_trace())
    assert readers.decode_roofline(run) == readers.decode_roofline(clean)


def test_percentile_counts_failures_as_infinite():
    assert readers.percentile([1.0, 2.0, 3.0, math.inf], 50) == 2.5
    assert readers.percentile([1.0, 2.0, 3.0, math.inf], 95) == math.inf
    assert readers.percentile([], 95) is None


# ------------------------------------------------------------ counts
def _config(name):
    """A configuration file by name; qwen2.5-3b is kept beside olmo-1b
    though no cell runs it (PERF.md, Open questions)."""
    return json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())


def _shape(name):
    return dd.Shape.of(_config(name))


def test_param_and_weight_counts_by_hand():
    q = _shape("qwen2.5-3b")
    per_layer = (2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008
                 + (2048 + 256 + 256) + 2 * 2048)
    assert dd.param_count(q) == 36 * per_layer + 151936 * 2048 + 2048 \
        == 3_085_938_688
    # the bytes of qwen2.5-3b's params measured on a v5e: 6.172 GB
    assert dd.weight_bytes(q) == 6_172_176_384
    o = _shape("olmo-1b")
    assert dd.param_count(o) == 16 * (4 * 2048 ** 2 + 3 * 2048 * 8192) \
        + 50304 * 2048 == 1_176_764_416
    assert dd.weight_bytes(o) == 2 * 1_176_764_416


def test_decode_cost_by_hand():
    q = _shape("qwen2.5-3b")
    # batch 16, 256 valid positions (new token at position 255)
    flops, nbytes = dd.decode_cost(q, 16, 255)
    kv = 36 * 2 * 2 * 128 * 2            # 36,864 B of K/V per token
    assert nbytes == 6_172_176_384 + 16 * (256 + 1) * kv
    matmul = 36 * (2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008) \
        + 151936 * 2048
    assert flops == 16 * (2 * matmul + 4 * 36 * 16 * 128 * 256)
    # a request's prompt of 256 and one served token: every prompt token
    # through the layers, the causal half of attention, one LM head
    assert dd.request_flops(q, 256, 1) == pytest.approx(
        2 * (matmul - 151936 * 2048) * 256 + 2 * 151936 * 2048
        + 4 * 36 * 16 * 128 * (256 * 257 / 2))


@pytest.mark.parametrize("name", ["qwen2.5-3b", "olmo-1b"])
def test_weights_match_the_programs_layout(name):
    from repro import models
    conf = _config(name)
    s = dd.Shape.of(conf)
    w = jax.eval_shape(lambda k: dd.make_weights(s, k),
                       jax.random.PRNGKey(0))
    ours = dd.program_params(s, w)
    theirs = jax.eval_shape(lambda k: models.init_params(
        k, dd.arch_config(conf)), jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    assert sum(x.size for x in jax.tree.leaves(w)) == dd.param_count(s)
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(w)) == \
        dd.weight_bytes(s)


# ------------------------------------------------------------ traffic
def test_open_loop_same_schedule_for_every_seed():
    t = tiny_cell().traffic
    a = traffic.open_loop(t, 10.0, 1, 512)
    b = traffic.open_loop(t, 10.0, 2**31 + 11, 512)
    assert len(a) == len(b) == 200
    assert [(p.due, p.max_new) for p in a] == [(p.due, p.max_new) for p in b]
    assert all(0 <= p.due < 10.0 for p in a)
    assert a[-1].due > 9.0
    assert a[5].prompt.tolist() != b[5].prompt.tolist()
    assert traffic.open_loop(t, 10.0, 1, 512)[5].prompt.tolist() == \
        a[5].prompt.tolist()
    # the output lengths are the distribution's quantiles, heavy tail kept
    lens = sorted(p.max_new for p in a)
    assert lens == sorted(traffic.output_lengths(t["output_len"], 200))
    assert lens[0] < t["output_len"]["median"] < lens[-1]


def test_backlog_batches_hold_the_same_lengths():
    t = tiny_cell("olmo-1b.offline-doc").traffic
    it = traffic.backlog(t, 7, 512)
    groups = [sorted(next(it).max_new for _ in range(4)) for _ in range(5)]
    assert all(g == groups[0] for g in groups)


# ------------------------------------------------------------ rehearsal
def test_cli_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_cli_refuses_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ has no served
    path to run: the run fails and prints no result."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_result_line(trace):
    out = tiny_run(tiny_cell(), trace=trace)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert set(out) - {"breakdown", "checks"} == {
        "correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 20
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in bm[kind]}
    assert set(out["metrics"]) <= allowed
    if not trace:
        assert {"latency_p50_s", "setup_s"} == \
            set(out["metrics"])
    else:
        assert out["metrics"]["compiles_in_window"]["value"] == 0
        assert "busy_s" in out["device"] and "window_s" in out["device"]
    json.loads(json.dumps(out))


def test_rehearsal_backlog_tokens_per_s():
    out = tiny_run(tiny_cell("olmo-1b.offline-doc"))
    assert out["correct"] is True
    assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert out["attempted"] % 4 == 0 and out["attempted"] > 0


def test_due_time_latency_charges_a_blocking_pump(monkeypatch):
    """Requests due while a pump blocks count the wait: their arrival is
    their due time, not the time they were routed."""
    hold = 0.4
    orig_pump = cell_mod.Server.warm

    def warm(self, seed):
        orig_pump(self, seed)
        pump = self.gateway.pump
        first = [True]

        def slow_pump(fn_id):
            got = pump(fn_id)
            if got and first[0]:
                first[0] = False
                time.sleep(hold)
            return got
        self.gateway.pump = slow_pump
    monkeypatch.setattr(cell_mod.Server, "warm", warm)
    server = cell_mod.Server(tiny_cell(), 5)
    server.warm(5)
    with cell_mod.CompileCounter() as counter:
        w = cell_mod.serve_window(server, 1.0, 5, counter, False)
    first = w["batches"][0]
    behind = [r for r in w["attempted"]
              if first.start < r.due < first.end - hold / 2]
    assert behind
    for r in behind:
        assert r.request.arrival == r.due
        assert r.request.completed_at - r.due >= first.end - r.due
        assert r.pump_start >= first.end


# ------------------------------------------------------------ the check
@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_check_on_every_seed(name):
    """At the tiny size, on three seeds, through the run's own check: the
    program comes out correct and the float8 control, in its place, not."""
    cell = tiny_cell(name)
    for seed in (1, 2, 3_000_000_019):
        rec = control.readings(cell, seed, 0.5)
        assert rec["correct"] is True, rec
        assert rec["control_correct"] is False, rec
        assert rec["served_gap"] <= TINY_LIMIT < rec["control_gap"]


def _fault(name):
    """Plants one fault in the served path, after warm-up."""
    def plant(engine):
        prefill, decode = engine._prefill, engine._decode
        if name == "state_unchanged":
            engine._decode = lambda p, t, pos, c: (decode(p, t, pos, c)[0], c)
        elif name == "half_batch_left_out":
            def half(p, batch):
                logits, cache = prefill(p, batch)
                B = batch["tokens"].shape[0]
                keep = jnp.arange(B) < B // 2
                cache = jax.tree.map(lambda x: x * keep.reshape(
                    (1, B) + (1,) * (x.ndim - 2)).astype(x.dtype), cache)
                return logits, cache
            engine._prefill = half
        elif name == "token_altered":
            def altered(p, t, pos, c):
                logits, c = decode(p, t, pos, c)
                return logits.at[:, :, 7].add(1e3), c
            engine._decode = altered
        else:
            raise ValueError(name)
    return plant


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch_left_out",
                                   "token_altered"])
def test_planted_fault_fails_the_check(fault, name, monkeypatch):
    orig = cell_mod.Server.warm

    def warm(self, seed):
        orig(self, seed)
        _fault(fault)(self.engine)
    monkeypatch.setattr(cell_mod.Server, "warm", warm)
    out = tiny_run(tiny_cell(name))
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > TINY_LIMIT


# ------------------------------------------------------------ v5e compile
@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


HBM = 16e9


@pytest.mark.parametrize("name", CELLS)
def test_cell_programs_fit_a_v5e(name, topo, no_persistent_cache):
    """The cell's largest prefill and decode, and its reference, compile
    for one v5e chip, and each fits beside the weights."""
    from jax.sharding import SingleDeviceSharding
    from repro.models import CallOpts
    from repro.training import steps
    one = SingleDeviceSharding(topo.devices[0])
    cell = spec.load_cell(name)
    s = dd.Shape.of(cell.config)
    cfg = dd.arch_config(cell.config)
    B, T = cell.traffic["pod"]["batch"], cell.traffic["pod"]["max_seq"]
    L = cell.traffic["prompt_len"]

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)
    w = on_chip(jax.eval_shape(lambda k: dd.make_weights(s, k),
                               jax.random.PRNGKey(0)))
    params = dd.program_params(s, w)
    prefill = jax.jit(steps.make_prefill_step(cfg, T, CallOpts()))
    decode = jax.jit(steps.make_decode_step(cfg, CallOpts()))
    toks = on_chip(jax.ShapeDtypeStruct((B, L), jnp.int32))
    _, cache = jax.eval_shape(prefill, params, {"tokens": toks})
    progs = {
        "prefill": prefill.lower(params, {"tokens": toks}),
        "decode": decode.lower(
            params, on_chip(jax.ShapeDtypeStruct((B, 1), jnp.int32)),
            on_chip(jax.ShapeDtypeStruct((), jnp.int32)), on_chip(cache)),
        "reference": jax.jit(dd.served_gaps, static_argnums=0).lower(
            s, w, on_chip(jax.ShapeDtypeStruct((T,), jnp.int32))),
    }
    for label, lowered in progs.items():
        m = lowered.compile().memory_analysis()
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes)
        assert need < 0.9 * HBM, (label, need)
