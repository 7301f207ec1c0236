"""The served path's own measurements: the ``BatchRecord`` each request of
a batch holds, the seconds slept in the libhas token acquire, and the
profiler spans of ``PodEngine.step``."""
import dataclasses
import glob
import time

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core import scheduler
from repro.core.scheduler import HASGPUScheduler, TokenLedger
from repro.core.vgpu import PodAlloc, VirtualGPU
from repro.serving import InferenceRequest, LibHas, PodEngine

SPANS = ("engine.batch", "engine.prefill", "engine.decode", "engine.sample",
         "engine.sync", "libhas.acquire")


@pytest.fixture(scope="module")
def olmo():
    from repro import models
    cfg = reduced(ARCHS["olmo-1b"])
    return cfg, models.init_params(jax.random.PRNGKey(0), cfg)


def _engine(cfg, params, batch=3, quota=1.0):
    vgpu = VirtualGPU("GPU-records", window_ms=20.0)
    pod = PodAlloc(fn_id="f", sm=vgpu.gpu_type.sm_total, quota=quota,
                   batch=batch)
    vgpu.place(pod)
    return PodEngine(cfg, pod, vgpu, HASGPUScheduler(), max_seq=32,
                     params=params)


def _submit(eng, cfg, max_new):
    """Requests that have waited past the batcher's timeout."""
    rng = np.random.default_rng(len(max_new))
    reqs = [InferenceRequest(
        prompt=rng.integers(2, cfg.vocab_size, size=6).astype(np.int32),
        max_new_tokens=m, arrival=time.monotonic() - 1.0) for m in max_new]
    for r in reqs:
        eng.submit(r)
    return reqs


class _SleepingClient:
    """Sleeps ``wait`` seconds on every acquire and says so."""

    def __init__(self, wait):
        self.wait = wait

    def acquire(self, cost_s):
        time.sleep(self.wait)
        return self.wait


def test_step_stamps_one_batch_record(olmo):
    cfg, params = olmo
    eng = _engine(cfg, params)
    reqs = _submit(eng, cfg, [1, 3, 2])
    assert all(r.batch_record is None for r in reqs)
    slept0 = eng.libhas.slept_s
    done = eng.step()
    assert done == reqs
    rec = reqs[0].batch_record
    assert all(r.batch_record is rec for r in reqs)
    assert rec.steps == 3
    assert rec.started <= rec.ended == reqs[0].completed_at
    assert 0.0 <= rec.turnaround_s <= rec.ended - rec.started
    assert rec.slept_s == pytest.approx(eng.libhas.slept_s - slept0)
    assert not hasattr(eng, "completed")
    # the next batch gets a record of its own, with a later id
    more = _submit(eng, cfg, [2])
    eng.step()
    assert more[0].batch_record is not rec
    assert more[0].batch_record.batch_id > rec.batch_id


def test_turnaround_leaves_out_the_acquire_sleep(olmo):
    """Every acquire sleeps 20 ms: the batch's record holds each sleep,
    and none of it is counted as turnaround."""
    cfg, params = olmo
    eng = _engine(cfg, params, batch=1)
    _submit(eng, cfg, [1])
    eng.step()                              # compiled outside the check
    eng.libhas.client = _SleepingClient(0.02)
    (req,) = _submit(eng, cfg, [3])
    eng.step()
    rec = req.batch_record
    # prefill + 3 decodes, and the last decode's charge at the batch's end
    assert rec.slept_s == pytest.approx(5 * 0.02)
    assert rec.turnaround_s + rec.slept_s <= rec.ended - rec.started


def test_libhas_slept_s_sums_what_the_client_returns():
    class Client:
        def __init__(self, waits):
            self.waits = list(waits)

        def acquire(self, cost_s):
            return self.waits.pop(0)

    lib = LibHas(client=Client([0.5, 0.0, 0.25]))
    for _ in range(3):
        lib.launch(lambda: None, cost_s=1.0)
    assert lib.slept_s == pytest.approx(0.75)
    assert lib.tokens_acquired_s == pytest.approx(3.0)
    lib.launch(lambda: None)                # no charge, no acquire
    assert lib.slept_s == pytest.approx(0.75)
    # a client that returns None (grants at once, says nothing) adds 0
    quiet = LibHas(client=Client([None, None]))
    quiet.launch(lambda: None, cost_s=1.0)
    quiet.launch(lambda: None, cost_s=1.0)
    assert quiet.slept_s == 0.0 and quiet.launches == 2


class _Clock:
    """A monotonic clock that moves only when told to or slept on."""

    def __init__(self, t=0.0):
        self.t = t
        self.slept = []

    def monotonic(self):
        return self.t

    def sleep(self, s):
        self.slept.append(s)
        self.t += s


def _client(monkeypatch, quota, clock):
    vgpu = VirtualGPU("G", window_ms=20.0)
    pod = PodAlloc(fn_id="f", sm=8, quota=quota, batch=1)
    vgpu.place(pod)
    monkeypatch.setattr(scheduler.time, "monotonic", clock.monotonic)
    monkeypatch.setattr(scheduler.time, "sleep", clock.sleep)
    return vgpu, pod, HASGPUScheduler().client_for(vgpu, pod.pod_id)


def test_gpu_client_acquire_returns_the_wait_it_slept(monkeypatch):
    """A charge is booked as the ``cost`` seconds just past: one inside
    the window's budget returns 0; one over it sleeps the rest of its
    windows off and returns that wait."""
    clock = _Clock(0.001)
    vgpu, pod, client = _client(monkeypatch, 0.5, clock)
    assert client.acquire(0.001) == 0.0 and clock.slept == []
    # the same history on a ledger of its own gives the expected wait
    ref = TokenLedger(vgpu)
    ref.acquire(pod.pod_id, 0.001, 0.0)
    clock.t += 0.05                         # a launch held the chip 50 ms
    want = ref.acquire(pod.pod_id, 0.05, 0.001) - clock.t
    got = client.acquire(0.05)              # 5 windows' budget at q=0.5
    assert want > 0.04
    assert got == pytest.approx(want) and clock.slept == [got]


@pytest.mark.parametrize("base", [0.0, 86_400.123])
def test_gpu_client_at_full_quota_never_sleeps(monkeypatch, base):
    """At quota 1.0 a run of disjoint measured intervals, short and
    longer than a window, across window edges, is paid without a
    sleep, however far the clock has run."""
    clock = _Clock(base)
    _, _, client = _client(monkeypatch, 1.0, clock)
    rng = np.random.default_rng(0)
    for i in range(200):
        clock.t += rng.choice([0.0, 1e-4, 0.0023])     # host gap
        busy = rng.choice([0.0057, 0.0239, 0.2596, 0.02, 1e-5])
        clock.t += busy
        assert client.acquire(busy) == 0.0, i
    assert clock.slept == []


def test_gpu_client_paces_measured_time_to_the_quota(monkeypatch):
    """At quota 0.5 with a 20 ms window, ten 30 ms launches sleep about
    as long again: the pod gets half of the wall time, within 10 %."""
    clock = _Clock(0.0)
    vgpu, pod, client = _client(monkeypatch, 0.5, clock)
    ref = TokenLedger(vgpu)
    want = 0.0
    for _ in range(10):
        clock.t += 0.03
        now = clock.t
        want += max(ref.acquire(pod.pod_id, 0.03, now - 0.03) - now, 0.0)
        client.acquire(0.03)
    slept = sum(clock.slept)
    assert slept == pytest.approx(want)
    assert slept == pytest.approx(0.3, rel=0.1)
    assert 0.3 / clock.t == pytest.approx(0.5, rel=0.1)


def test_step_charges_measured_time_at_full_quota(olmo):
    """A pod at quota 1.0 never sleeps: each launch pays what it held
    the device, and the batch's charge lies inside its wall time."""
    cfg, params = olmo
    eng = _engine(cfg, params, batch=2)
    _submit(eng, cfg, [1])
    eng.step()                              # compiled outside the check
    charged0 = eng.libhas.tokens_acquired_s
    (req, _) = _submit(eng, cfg, [4, 2])
    eng.step()
    rec = req.batch_record
    assert rec.slept_s == 0.0
    assert 0.0 < rec.charged_s <= rec.ended - rec.started
    assert eng.libhas.tokens_acquired_s - charged0 == pytest.approx(
        rec.charged_s)


class _Unmetered:
    """Grants every acquire at once and says nothing."""

    def acquire(self, cost_s):
        return None


def test_step_serves_through_a_client_that_only_acquires(olmo):
    """A client with nothing but ``acquire(cost_s) -> None`` swapped in
    through ``dataclasses.replace`` serves a batch; the charge is still
    counted."""
    cfg, params = olmo
    eng = _engine(cfg, params, batch=2)
    eng.libhas = dataclasses.replace(eng.libhas, client=_Unmetered())
    reqs = _submit(eng, cfg, [2, 3])
    assert eng.step() == reqs
    rec = reqs[0].batch_record
    assert all(len(r.output) == r.max_new_tokens for r in reqs)
    assert rec.slept_s == 0.0 and rec.charged_s > 0.0
    assert eng.libhas.tokens_acquired_s == pytest.approx(rec.charged_s)


def _host_spans(log_dir):
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name in SPANS]


def test_step_spans_in_a_profiler_trace(olmo, tmp_path):
    cfg, params = olmo
    eng = _engine(cfg, params, batch=2)
    _submit(eng, cfg, [2, 2])
    eng.step()                              # compiled outside the trace
    reqs = _submit(eng, cfg, [1, 2])
    with jax.profiler.trace(str(tmp_path)):
        eng.step()
    spans = _host_spans(tmp_path)
    assert {s[0] for s in spans} == set(SPANS)
    (batch,) = [s for s in spans if s[0] == "engine.batch"]
    assert batch[3] == {"batch": reqs[0].batch_record.batch_id, "rows": 2,
                        "steps": 2,
                        "reqs": " ".join(str(r.req_id) for r in reqs)}
    for name in ("engine.decode", "engine.sync", "libhas.acquire"):
        inner = [s for s in spans if s[0] == name]
        assert inner and all(batch[1] <= s[1] <= s[2] <= batch[2]
                             for s in inner), name
    count = {n: sum(s[0] == n for s in spans) for n in SPANS}
    assert count["engine.decode"] == 2
    assert count["engine.sync"] == 3        # 2 tokens + the last decode
    assert count["libhas.acquire"] == 4     # prefill + 2 decodes + the end
