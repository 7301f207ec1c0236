"""CPU rehearsal of ``chip_smoke.py``: its serving phase on the reduced
qwen2.5-3b, with interpret-mode kernels, and its refusal to run off a TPU."""
import jax
import numpy as np
import pytest

import chip_smoke
from repro.configs import ARCHS, reduced
from repro.models import CallOpts

SMALL = dict(max_seq=128, prompt_lens=(16, 32), batch=2, n_requests=4,
             new_tokens=3)


@pytest.fixture(scope="module")
def qwen_reduced():
    cfg = reduced(ARCHS["qwen2.5-3b"])
    return cfg, chip_smoke.init_params(cfg)


@pytest.fixture(scope="module")
def served(qwen_reduced):
    cfg, params = qwen_reduced
    return {label: chip_smoke.serve(cfg, params, opts, **SMALL)
            for label, opts in (("xla", CallOpts()),
                                ("pallas", CallOpts(use_kernels=True)))}


@pytest.mark.parametrize("label", ["xla", "pallas"])
def test_serve_phase_returns_every_request(served, qwen_reduced, label):
    cfg, _ = qwen_reduced
    run = served[label]
    reqs = run["requests"]
    assert len(reqs) == SMALL["n_requests"]
    assert len(run["batch_s"]) == SMALL["n_requests"] // SMALL["batch"]
    assert [len(r.prompt) for r in reqs] == [16, 16, 32, 32]
    for r in reqs:
        assert r.output.shape == (SMALL["new_tokens"],)
        assert 0 <= r.output.min() and r.output.max() < cfg.vocab_size
        assert r.latency is not None and r.latency > 0
    assert run["first_logits"].shape == (SMALL["batch"], cfg.vocab_size)
    assert np.isfinite(run["first_logits"]).all()


def test_serve_phase_kernel_logits_agree_with_xla(served):
    err = chip_smoke.rel_err(served["pallas"]["first_logits"],
                             served["xla"]["first_logits"])
    assert err <= chip_smoke.LOGITS_RTOL


def test_interpreted_kernels_are_not_counted_as_on_path(qwen_reduced):
    """Off the TPU the kernels run in interpret mode, which lowers to no
    Mosaic call: the check chip_smoke makes on the chip would fail."""
    cfg, params = qwen_reduced
    on_path = chip_smoke.pallas_calls_on_path(
        cfg, params, CallOpts(use_kernels=True), SMALL["max_seq"], 16,
        batch=SMALL["batch"])
    assert on_path == {"prefill": False, "decode": False}


def test_main_refuses_a_machine_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main() == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_serve_phase_rejects_a_short_output(qwen_reduced, monkeypatch):
    """A request that comes back with fewer tokens than it asked for fails
    the phase (the check that guards the chip run)."""
    from repro.serving import engine as engine_mod
    cfg, params = qwen_reduced
    real_step = engine_mod.PodEngine.step

    def truncating_step(self):
        done = real_step(self)
        for r in done:
            r.output = r.output[:-1]
        return done

    monkeypatch.setattr(engine_mod.PodEngine, "step", truncating_step)
    with pytest.raises(SystemExit, match="output"):
        chip_smoke.serve(cfg, params, CallOpts(), **SMALL)
