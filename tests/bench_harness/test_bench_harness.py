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

WORKLOADS = tuple(w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"])
TINY_POD = {"batch": 4, "max_seq": 32, "quota": 1.0}
TINY_LEN = {"kind": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
            "max": 16}
# A bursty mix's timeline at a tenth: in a 1 s window, 3 requests at the
# base rate, 6 in the 0.4-0.6 s burst, 3 after it.
TINY_BURSTS = {"kind": "bursty", "base_per_s": 8.0, "burst_factor": 4,
               "period_s": 1.0, "burst_s": 0.2, "first_burst_s": 0.4}
# The tiny cells' limit, from their own CPU readings over seeds 1, 2 and
# 3,000,000,019 of both: sound runs read at most 2.1e-3, the float8
# control at least 3.4e-2.
TINY_LIMIT = 2e-2


def tiny(cell: spec.Cell, **check) -> spec.Cell:
    """``cell`` at a tiny size: its family's tiny configuration, a tiny
    pod, and its arrivals' kind at a rate a CPU serves."""
    t = dict(cell.traffic, prompt_len=16, pod=TINY_POD, output_len=TINY_LEN)
    kind = t["arrivals"]["kind"]
    if kind == "poisson":
        t["arrivals"] = {"kind": "poisson", "rate_per_s": 20.0}
    elif kind == "bursty":
        t["arrivals"] = TINY_BURSTS
    return dataclasses.replace(
        cell, config=cell.family.tiny(cell.config), traffic=t,
        check={"check_requests": 8, "logit_gap_limit": TINY_LIMIT, **check})


def tiny_cell(name: str = WORKLOADS[0], **check) -> spec.Cell:
    """The cell ``name`` of ``BENCHMARK.json`` at a tiny size."""
    return tiny(spec.load_cell(name), **check)


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


def with_program_spans(t: tr.Trace) -> tr.Trace:
    """``t`` with the program's spans inside its two pumps."""
    prog = [("engine.batch", 9.2, 39.5), ("engine.prefill", 9.5, 10.5),
            ("libhas.acquire", 29.5, 30.5), ("engine.batch", 59.0, 89.5),
            ("engine.sync", 88.0, 89.2)]
    return tr.Trace(t.modules, t.ops, sorted(t.spans + prog,
                                             key=lambda e: e[1]))


def test_idle_gaps_credited_to_the_innermost_program_span():
    t = with_program_spans(synthetic_trace())
    gaps = tr.idle_gaps(t)
    assert [g[1] for g in gaps[:3]] == [26e-9, 22e-9, 10e-9]
    # the middle of 78-100 lies in engine.sync, in engine.batch, in a pump
    assert [g[0] for g in gaps[:3]] == ["none", "engine.sync", "route"]
    by_length = {g[1]: g[0] for g in gaps}
    assert by_length[1e-9] == "engine.batch"              # 25-26
    assert tr.span_at(t, 30) == "libhas.acquire"
    assert tr.span_at(t, 21) == "engine.batch"
    assert tr.span_at(t, 39.8) == "pump" and tr.span_at(t, 45) == "none"
    # the host gaps still group by the benchmark's pump
    dec = tr.programs(t, "decode_step")
    assert [len(g) for g in tr.grouped_by_span(t, dec, "pump")] == [3, 2]
    run = _fake_run([_batch([3, 1], 1.0, 2.0), _batch([2], 2.0, 3.0)], t)
    assert readers.decode_host_gap_ms(run) == pytest.approx(5 / 3 * 1e-6)


def test_load_keeps_the_program_spans(tmp_path):
    """A CPU profiler trace: ``load`` keeps the benchmark's and the
    program's spans and no other host event."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("engine.batch", rows=2):
                with jax.profiler.TraceAnnotation("libhas.acquire"):
                    jnp.ones(3).block_until_ready()
                with jax.profiler.TraceAnnotation("engine.sync"):
                    pass
            with jax.profiler.TraceAnnotation("other.span"):
                pass
    finally:
        jax.profiler.stop_trace()
    names = [s[0] for s in tr.load(str(tmp_path)).spans]
    assert names == ["bench.window", "engine.batch", "libhas.acquire",
                     "engine.sync"]


def _fake_run(batches, trace=None):
    cell = dataclasses.replace(tiny_cell())
    return cell_mod.Run(
        cell=cell, shape=dd.Shape.of(dd.TINY),
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


def test_readers_keep_a_first_prefill_that_starts_before_the_window():
    """The device's clock runs behind the host's: the first traced
    batch's prefill, dispatched just after the window opens, can start
    before it on the trace's clock. It is still that batch's program."""
    t = synthetic_trace()
    early = [("jit_prefill_step", -2, 20) if m[1] == 10 else m
             for m in t.modules]
    t = tr.Trace(early, t.ops, t.spans)
    run = _fake_run([_batch([3, 1], 1.0, 2.0), _batch([2], 2.0, 3.0)], t)
    assert len(tr.programs(t, "prefill_step")) == 2
    assert readers.program_ms(run, "prefill_step") == pytest.approx(16e-6)
    # a program that ended before the window opened is not in it
    gone = tr.Trace([("jit_prefill_step", -9, -1)] + t.modules, t.ops,
                    t.spans)
    assert len(tr.programs(gone, "prefill_step")) == 2


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


def test_poisson_and_backlog_schedules_are_pinned():
    """The chat and offline-doc mixes make what they made before bursty
    arrivals came in: the same due times, lengths and prompts."""
    import hashlib

    def digest(x):
        return hashlib.sha256(repr(x).encode()).hexdigest()[:16]
    chat = spec.load_cell("olmo-1b.chat").traffic
    for seconds, n, want in ((51.0, 82, "967c46e3cd2c317c"),
                             (10.0, 16, "12514fddda88bd86")):
        got = traffic.open_loop(chat, seconds, 12345, 50304)
        assert len(got) == n
        assert digest([(p.due, p.max_new) for p in got]) == want
        assert digest([p.prompt.tolist() for p in got[:3]]) == \
            "bee2276f880b5710"
    it = traffic.backlog(spec.load_cell("olmo-1b.offline-doc").traffic, 7,
                         50304)
    assert digest([(p.max_new, p.prompt[:4].tolist())
                   for p in (next(it) for _ in range(32))]) == \
        "b0a58eab79d244b7"


def test_bursty_schedule_same_for_every_seed():
    t = spec.load_cell("olmo-1b.chat-burst").traffic
    arr = t["arrivals"]
    base, burst = arr["base_per_s"], arr["burst_factor"] * arr["base_per_s"]
    parts = traffic.stretches(arr, 51.0)
    assert [(a, b) for a, b, _ in parts] == [
        (0, 4), (4, 6), (6, 14), (14, 16), (16, 24), (24, 26), (26, 34),
        (34, 36), (36, 44), (44, 46), (46, 51)]
    for i, (a, b, n) in enumerate(parts):
        assert n == round((burst if i % 2 else base) * (b - a))
    a = traffic.open_loop(t, 51.0, 1, 50304)
    b = traffic.open_loop(t, 51.0, 2**31 + 11, 50304)
    assert len(a) == sum(n for *_, n in parts)
    assert [(p.due, p.max_new) for p in a] == [(p.due, p.max_new) for p in b]
    assert a[5].prompt.tolist() != b[5].prompt.tolist()
    due = np.array([p.due for p in a])
    assert (np.diff(due) > 0).all()
    for lo, hi, n in parts:       # each stretch holds its count
        inside = due[(due >= lo) & (due < hi)]
        assert len(inside) == n and inside[0] == lo
    in_bursts = sum(n for i, (*_, n) in enumerate(parts) if i % 2)
    assert 0.4 < in_bursts / len(a) < 0.6


def test_bursty_output_lengths_are_the_quantiles():
    t = spec.load_cell("olmo-1b.chat-burst").traffic
    due, lens = traffic.schedule(t, 51.0)
    assert sorted(lens) == sorted(traffic.output_lengths(t["output_len"],
                                                         len(due)))
    assert 16 <= lens.min() and lens.max() <= 256
    assert np.median(lens) == pytest.approx(t["output_len"]["median"],
                                            abs=1)
    # the shuffle is one for the window, not one a stretch: a burst's
    # lengths are no sorted run
    assert list(lens[:10]) != sorted(lens[:10])


def test_a_short_window_holds_the_first_stretches_only():
    for seconds, edges, counts in ((1.0, [0, 0.4, 0.4, 0.6, 0.6, 1.0],
                                    [3, 6, 3]),
                                   (0.5, [0, 0.4, 0.4, 0.5], [3, 3])):
        got = traffic.stretches(TINY_BURSTS, seconds)
        assert [x for a, b, _ in got for x in (a, b)] == pytest.approx(edges)
        assert [n for *_, n in got] == counts
    with pytest.raises(ValueError):
        traffic.stretches({"kind": "backlog"}, 1.0)


def test_sweep_steady_form_and_knee():
    from bench import sweep
    cell = spec.load_cell("olmo-1b.chat-burst")
    flat = sweep.steady(cell)
    assert flat.traffic["arrivals"] == {"kind": "poisson"}
    assert flat.traffic["output_len"] == cell.traffic["output_len"]
    assert flat.traffic["pod"] == cell.traffic["pod"]
    due, lens = traffic.schedule(flat.traffic, 51.0, rate_per_s=2.0)
    assert len(due) == 102
    assert cell.traffic["arrivals"]["kind"] == "bursty"

    def rec(rate, queued=0, halves=(1.0, 1.1)):
        return {"rate_per_s": rate, "queued_at_close": queued,
                "failed": 0, "first_half_mean_s": halves[0],
                "second_half_mean_s": halves[1]}
    assert sweep.knee([rec(4.0), rec(2.0), rec(6.0, queued=3)]) == 4.0
    # halves apart below the knee, with no queue left, do not stop it
    assert sweep.knee([rec(2.0, halves=(1.0, 1.5)), rec(4.0),
                       rec(6.0, halves=(1.0, 1.3)), rec(8.0, queued=1)]) \
        == 4.0
    assert sweep.knee([rec(2.0, queued=1), rec(4.0)]) == 0.0
    assert sweep.knee([rec(2.0, halves=(1.0, 1.3))]) == 0.0


# ------------------------------------------------------------ rehearsal
def test_cli_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0], "--seed",
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
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0], "--seed",
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


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_burst_cell(trace):
    out = tiny_run(tiny_cell("olmo-1b.chat-burst"), trace=trace)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 12          # 3 + 6 in the burst + 3
    if not trace:
        assert set(out["metrics"]) == {"latency_p50_s", "setup_s"}
    else:
        m = out["metrics"]
        assert m["compiles_in_window"]["value"] == 0
        assert 0 < m["batch_fill"]["value"] <= 100
        assert "queue_wait_p95_ms" in m and "admit_wait_p50_ms" in m


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
def assert_control_fails(cell: spec.Cell) -> None:
    """At the tiny size, on three seeds, through the run's own check: the
    program comes out correct and the float8 control, in its place, not."""
    for seed in (1, 2, 3_000_000_019):
        rec = control.readings(cell, seed, 0.5)
        assert rec["correct"] is True, rec
        assert rec["control_correct"] is False, rec
        assert rec["served_gap"] <= TINY_LIMIT < rec["control_gap"]


@pytest.mark.parametrize("name", WORKLOADS)
def test_control_fails_the_check_on_every_seed(name):
    assert_control_fails(tiny_cell(name))


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


FAULTS = ("state_unchanged", "half_batch_left_out", "token_altered")


def assert_fault_fails(cell: spec.Cell, fault: str, monkeypatch) -> None:
    orig = cell_mod.Server.warm

    def warm(self, seed):
        orig(self, seed)
        _fault(fault)(self.engine)
    monkeypatch.setattr(cell_mod.Server, "warm", warm)
    out = tiny_run(cell)
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > TINY_LIMIT


@pytest.mark.parametrize("name", WORKLOADS)
@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_fails_the_check(fault, name, monkeypatch):
    assert_fault_fails(tiny_cell(name), fault, monkeypatch)


STAND_IN_FAMILY = '''"""A stand-in family: the dense decoder under a
name of its own, with a tiny configuration of its own."""
from bench.families.dense_decoder import (  # noqa: F401
    TINY, Shape, arch_config, decode_cost, logit_gaps, make_weights,
    program_params, request_flops, served_gaps)


def tiny(conf):
    return dict(TINY, norm=conf["norm"], qkv_bias=conf["qkv_bias"],
                num_hidden_layers=2)
'''


def test_a_new_family_cell_from_new_files_alone(tmp_path, monkeypatch):
    """A cell of a family the harness has not seen, added as new files
    and ``BENCHMARK.json`` entries beside a copy of ``bench/``, loads and
    passes the per-cell checks at the tiny size."""
    import shutil
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "families" / "stand_in_decoder.py").write_text(STAND_IN_FAMILY)
    conf = dict(_config("olmo-1b"), name="stand-in",
                family="stand_in_decoder")
    (bench / "configs" / "stand-in.json").write_text(json.dumps(conf))
    mix = dict(json.loads((bench / "traffic" / "chat-burst.json")
                          .read_text()), prompt_len=128)
    (bench / "traffic" / "short-burst.json").write_text(json.dumps(mix))
    (bench / "cells" / "stand-in.short-burst.json").write_text(
        json.dumps({"check_requests": 8, "logit_gap_limit": 0.15}))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "stand-in", "source": "test",
                          "file": "bench/configs/stand-in.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "stand-in.short-burst",
                            "config": "stand-in", "traffic": "short-burst",
                            "chips": 1, "why": "test"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in ("latency_p50_s", "batch_fill"):
            m["workloads"].append("stand-in.short-burst")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = spec.load_cell("stand-in.short-burst",
                          benchmark=tmp_path / "BENCHMARK.json",
                          bench_dir=bench)
    assert cell.family.__file__ == str(bench / "families"
                                       / "stand_in_decoder.py")
    assert cell.traffic["arrivals"]["kind"] == "bursty"
    small = tiny(cell)
    assert small.config["num_hidden_layers"] == 2
    out = tiny_run(small)
    assert out["correct"] is True and out["attempted"] == 12
    assert set(out["metrics"]) == {"latency_p50_s", "setup_s"}
    assert "batch_fill" in tiny_run(small, trace=1)["metrics"]
    assert_control_fails(small)
    assert_fault_fails(small, "token_altered", monkeypatch)


def test_arch_config_passes_the_norm_eps_where_the_program_takes_it(
        monkeypatch):
    """Where the program's ``ArchConfig`` has a ``norm_eps``, the
    configuration's own eps goes in (qwen2.5-3b publishes 1e-6)."""
    from repro.configs import base
    plain = dd.arch_config(_config("qwen2.5-3b"))
    assert plain.d_model == 2048
    stand_in = dataclasses.make_dataclass(
        "ArchConfig", [("norm_eps", float, 1e-5)], bases=(base.ArchConfig,),
        frozen=True)
    monkeypatch.setattr(base, "ArchConfig", stand_in)
    assert dd.arch_config(_config("qwen2.5-3b")).norm_eps == 1e-6
    assert dd.arch_config(_config("olmo-1b")).norm_eps == 1e-5
    assert dd.arch_config(_config("qwen2.5-3b")).d_model == 2048


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


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_programs_fit_a_v5e(name, topo, no_persistent_cache):
    """The cell's largest prefill and decode, and its reference, compile
    for one v5e chip, and each fits beside the weights."""
    from jax.sharding import SingleDeviceSharding
    from repro.models import CallOpts
    from repro.training import steps
    one = SingleDeviceSharding(topo.devices[0])
    cell = spec.load_cell(name)
    fam = cell.family
    s = fam.Shape.of(cell.config)
    cfg = fam.arch_config(cell.config)
    B, T = cell.traffic["pod"]["batch"], cell.traffic["pod"]["max_seq"]
    L = cell.traffic["prompt_len"]

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)
    w = on_chip(jax.eval_shape(lambda k: fam.make_weights(s, k),
                               jax.random.PRNGKey(0)))
    params = fam.program_params(s, w)
    prefill = jax.jit(steps.make_prefill_step(cfg, T, CallOpts()))
    decode = jax.jit(steps.make_decode_step(cfg, CallOpts()))
    toks = on_chip(jax.ShapeDtypeStruct((B, L), jnp.int32))
    _, cache = jax.eval_shape(prefill, params, {"tokens": toks})
    progs = {
        "prefill": prefill.lower(params, {"tokens": toks}),
        "decode": decode.lower(
            params, on_chip(jax.ShapeDtypeStruct((B, 1), jnp.int32)),
            on_chip(jax.ShapeDtypeStruct((), jnp.int32)), on_chip(cache)),
        "reference": jax.jit(fam.served_gaps, static_argnums=0).lower(
            s, w, on_chip(jax.ShapeDtypeStruct((T,), jnp.int32))),
    }
    for label, lowered in progs.items():
        m = lowered.compile().memory_analysis()
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes)
        assert need < 0.9 * HBM, (label, need)
