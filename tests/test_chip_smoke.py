"""`chip_smoke.py` on the CPU: its phases at a tiny size, and its refusal to
run (or print a result) without a TPU."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_tiny_in_interpret_mode(smoke, monkeypatch):
    """Build, serve two full batches and a partial one, check order, drops
    and the raw-head alignment — with every kernel interpreted, so the
    served bundle holds no Mosaic kernel."""
    from repro.kernels.config import KernelConfig
    monkeypatch.setattr(KernelConfig, "resolved_interpret", lambda self: True)
    obs = smoke.serve_phase(size=64, slots=2, n_requests=5)
    assert obs["served"] == 5 and obs["dropped"] == 0
    assert obs["custom_calls"] == 0
    assert obs["alignment"].max_abs < smoke.RAW_LSB
    assert obs["alignment"].within_1lsb == 1.0
    assert obs["conv1_off"] == (0, 0)


def test_four_chip_phase_on_virtual_devices(smoke):
    out = smoke.four_chip_phase(jax.devices()[:4])
    assert out["sharded_loss_diff"] < smoke.LOSS_TOL
    assert out["pipeline_grad_rel_err"] < smoke.GRAD_REL_TOL
    assert out["pipeline_lm_loss_diff"] < smoke.LOSS_TOL


def test_failing_check_raises(smoke):
    with pytest.raises(smoke.SmokeFailure, match="boom"):
        smoke.check(False, "boom")


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in-repo", "alone-in-a-directory"])
def test_exits_nonzero_without_tpu(tmp_path, alone):
    script = SCRIPT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if alone:
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
        env.pop("PYTHONPATH", None)
    res = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
