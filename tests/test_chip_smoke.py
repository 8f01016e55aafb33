"""chip_smoke.py: refuses to run anywhere but on a TPU, and its selection
and serving phases hold at tiny sizes with the Pallas kernels in
interpret mode, so the script cannot rot between chip runs."""

import os
import shutil
import subprocess
import sys

import pytest

from repro.kernels import ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


@pytest.mark.parametrize("where,argv", [
    ("repo", []), ("repo", ["--four-chips"]), ("alone", [])])
def test_refuses_without_chip_or_repo(tmp_path, where, argv):
    script = SCRIPT
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    r = subprocess.run([sys.executable, script, *argv], env=env,
                       cwd=str(tmp_path), capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.fixture
def interpret_kernels():
    ops.set_backend("interpret")
    try:
        yield
    finally:
        ops.set_backend(None)


def test_selection_phase_tiny(interpret_kernels):
    ratios = chip_smoke.phase_selection(dict(
        n=256, d=64, k=16, k_craig=24, chunk=64, n_parity=128, k_parity=12,
        seed=0), timeout=600)
    assert set(ratios) == {"gradmatch", "gradmatch-stream", "craig-lazy"}


def test_serving_phase_tiny(interpret_kernels):
    rep = chip_smoke.phase_serving(dict(
        n=256, d=64, pools=2, requests=8, tenants=2, k=16, k_extend=32,
        seed=1))
    assert rep["tickets"] == ["done/certified/b4"] * 8
    assert rep["identical_to_single"] == 8
    assert rep["extension_identical"]


def test_selection_phase_fails_outside_band(interpret_kernels, monkeypatch):
    monkeypatch.setattr(chip_smoke, "OBJECTIVE_BAND", -1.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="objective ratio"):
        chip_smoke.phase_selection(dict(
            n=256, d=64, k=16, k_craig=24, chunk=64, n_parity=128,
            k_parity=12, seed=0), timeout=600)


def test_lm_train_phase_smoke():
    """The xlstm phase's argv (clip included) through launch/train.main at
    the smoke config: six steps, two selection rounds, finite losses."""
    rep = chip_smoke.phase_lm_train(chip_smoke.LM_TRAIN, smoke=True)
    assert rep["arch"] == "xlstm-1.3b"
    assert len(rep["losses"]) == chip_smoke.LM_TRAIN["steps"]
    assert len(rep["rounds"]) == 2


def test_run_phase_splits_compile_from_run_time(capsys):
    """A phase that compiles a new program reports its compile seconds
    from the program's own counter, in the same fields as before."""
    import jax
    import jax.numpy as jnp

    def phase():
        return jax.jit(lambda v: jnp.cos(v) * 7.0 - 0.5)(jnp.arange(5.0))

    c0 = chip_smoke.compile_seconds()
    out = chip_smoke.run_phase("tiny", phase)
    assert chip_smoke.compile_seconds() > c0
    line = capsys.readouterr().out.strip()
    assert out.shape == (5,)
    name, fields = line.split(": ", 1)
    assert name == "[phase] tiny"
    got = dict(kv.split("=") for kv in fields.split())
    assert list(got) == ["wall_s", "compile_s", "run_s"]
    assert float(got["wall_s"]) == pytest.approx(
        float(got["compile_s"]) + float(got["run_s"]), abs=0.02)
