"""The per-layer metrics that read the program's own batch records, and
the program names the device-trace readers find the steps by.

    JAX_PLATFORMS=cpu python -m pytest -q tests/bench_harness
"""
from __future__ import annotations

import math
import sys
import types
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
from bench import records, spec, traffic  # noqa: E402
from repro.serving.batcher import BatchRecord, InferenceRequest  # noqa: E402

READERS = ("libhas_sleep_share", "libhas_sleep_share.offline",
           "token_turnaround_ms", "token_turnaround_ms.offline",
           "admit_wait_p50_ms", "batch_fill")
POD_OF_4 = types.SimpleNamespace(traffic={"pod": {"batch": 4}})


def _read(name, run):
    return spec.load_module(spec.BENCH / "metrics" / f"{name}.py").read(run)


def _req(arrival, rec=None, max_new=2):
    req = InferenceRequest(prompt=np.zeros(4, np.int32),
                           max_new_tokens=max_new, arrival=arrival)
    req.batch_record = rec
    if rec is not None:
        req.completed_at = rec.ended
    return cell_mod.Req(traffic.Planned(arrival, req.prompt, max_new),
                        due=arrival, request=req, pump_start=arrival)


def _run(batches, attempted):
    return cell_mod.Run(
        cell=POD_OF_4, shape=None, peaks={}, t_start=0.0, t_window=0.0,
        t_last_end=batches[-1].end if batches else 0.0,
        attempted=attempted, batches=batches, compiles_in_window=0)


def _batch(rec, reqs):
    return cell_mod.Batch(rec.started, rec.ended, 4, rec.steps, reqs)


def synthetic_run():
    """Two batches: 10.0-12.0 s (4 steps, 0.5 s slept, 8 ms of
    turnaround) and 13.0-14.0 s (6 steps, 0 s slept, 22 ms); a third
    request arrived and was never served."""
    a = BatchRecord(batch_id=0, steps=4, started=10.0, ended=12.0,
                    slept_s=0.5, turnaround_s=0.008)
    b = BatchRecord(batch_id=1, steps=6, started=13.0, ended=14.0,
                    slept_s=0.0, turnaround_s=0.022)
    first = [_req(9.9, a), _req(9.6, a)]
    second = [_req(12.0, b)]
    lost = _req(13.5)
    return _run([_batch(a, first), _batch(b, second)],
                first + second + [lost])


def test_readers_on_hand_set_records():
    run = synthetic_run()
    got = {n: _read(n, run) for n in READERS}
    assert got["libhas_sleep_share"] == pytest.approx(100 * 0.5 / 3.0)
    assert got["libhas_sleep_share.offline"] == got["libhas_sleep_share"]
    assert got["token_turnaround_ms"] == pytest.approx(1e3 * 0.030 / 10)
    assert got["token_turnaround_ms.offline"] == \
        got["token_turnaround_ms"]
    # waits 100, 400, 1000 ms and inf: the median lies between 400 and
    # 1000
    assert records.admit_waits_ms(run) == pytest.approx(
        [100.0, 400.0, 1000.0, math.inf])
    assert got["admit_wait_p50_ms"] == pytest.approx(700.0)
    # rows 2 and 1 of a pod of 4
    assert got["batch_fill"] == pytest.approx(100 * 1.5 / 4)


def test_batch_fill_counts_the_requests_stamped_with_each_record():
    """Rows are the attempted requests that hold a batch's record, not
    the requests the benchmark filed under it."""
    run = synthetic_run()
    a = run.batches[0].reqs[0].request.batch_record
    run.attempted.append(_req(9.95, a))      # a third row of batch a
    assert _read("batch_fill", run) == pytest.approx(100 * 2.0 / 4)
    full = synthetic_run()
    full.cell = types.SimpleNamespace(traffic={"pod": {"batch": 2}})
    assert _read("batch_fill", full) == pytest.approx(100 * 1.5 / 2)


def test_admit_wait_median_is_inf_when_most_were_not_served():
    run = synthetic_run()
    run.attempted += [_req(13.6), _req(13.7)]
    assert _read("admit_wait_p50_ms", run) == math.inf


def test_readers_read_nothing_from_a_program_without_records():
    """A program that keeps no batch record (no ``batch_record`` on its
    requests) gives no reading, and no reader raises."""
    def bare(arrival):
        req = type("R", (), {"arrival": arrival, "completed_at": 12.0,
                             "output": None})()
        return cell_mod.Req(traffic.Planned(arrival, np.zeros(4), 2),
                            due=arrival, request=req, pump_start=arrival)

    reqs = [bare(9.0), bare(9.5)]
    run = _run([cell_mod.Batch(10.0, 12.0, 4, 2, reqs)], reqs)
    assert {n: _read(n, run) for n in READERS} == dict.fromkeys(READERS)
    empty = _run([], [])
    assert {n: _read(n, empty) for n in READERS} == dict.fromkeys(READERS)


def test_step_programs_keep_the_names_traces_read():
    """The device-trace readers find the step programs by the substrings
    ``prefill_step`` and ``decode_step`` of their module names."""
    from repro import models
    from repro.configs import ARCHS, reduced
    from repro.models import CallOpts
    from repro.serving.engine import compiled_steps

    cfg = reduced(ARCHS["olmo-1b"])
    params = jax.eval_shape(lambda k: models.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    prefill, decode = compiled_steps(cfg, 32, CallOpts())
    batch = {"tokens": jax.ShapeDtypeStruct((2, 8), jnp.int32)}
    logits, cache = jax.eval_shape(prefill, params, batch)
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    low_p = prefill.lower(params, batch).as_text()
    low_d = decode.lower(params, tok, pos, cache).as_text()
    assert "module @jit_prefill_step" in low_p
    assert "module @jit_decode_step" in low_d
