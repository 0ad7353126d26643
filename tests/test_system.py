"""End-to-end system tests: training driver with checkpoint/resume,
watchdog, and the paper pipeline as one flow."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

_ROOT = Path(__file__).resolve().parents[1]
_ENV = dict(os.environ, PYTHONPATH=str(_ROOT / "src"))


def _train(args, timeout=900):
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.train", *args],
        capture_output=True, text=True, timeout=timeout, env=_ENV)


@pytest.mark.slow
def test_train_loss_decreases(tmp_path):
    r = _train(["--arch", "qwen3-4b", "--smoke", "--steps", "25",
                "--batch", "4", "--seq", "32"])
    assert r.returncode == 0, r.stderr[-2000:]
    losses = [float(l.split("loss=")[1].split()[0])
              for l in r.stdout.splitlines() if "loss=" in l]
    assert losses[-1] < losses[0], losses


@pytest.mark.slow
def test_crash_and_resume_continues_from_checkpoint(tmp_path):
    ck = tmp_path / "ck"
    common = ["--arch", "qwen3-4b", "--smoke", "--steps", "30", "--batch", "4",
              "--seq", "32", "--ckpt-dir", str(ck), "--ckpt-every", "10"]
    r1 = _train(common + ["--simulate-failure", "15"])
    assert r1.returncode == 17, (r1.returncode, r1.stderr[-1000:])
    assert "SIMULATED NODE FAILURE" in r1.stdout

    r2 = _train(common)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from checkpoint at step 10" in r2.stdout
    # the resumed run must not start from step 0
    steps = [int(l.split("step")[1].split()[0]) for l in r2.stdout.splitlines()
             if l.startswith("[train] step")]
    assert min(steps) >= 10


@pytest.mark.slow
def test_paper_pipeline_end_to_end():
    """quickstart example runs green: train -> PTQ -> LUT -> timing model."""
    r = subprocess.run([sys.executable, str(_ROOT / "examples" / "quickstart.py")],
                       capture_output=True, text=True, timeout=900, env=_ENV)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "n_total=5332" in r.stdout


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(tmp_path, env_dir):
    """The entry points' cache sits at the fixed ``<checkout>/.jax_cache``,
    or wherever ``JAX_COMPILATION_CACHE_DIR`` says (checked in a child, so
    this process keeps its cache settings)."""
    env = {k: v for k, v in _ENV.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    r = subprocess.run(
        [sys.executable, "-c", "from repro.compile_cache import "
         "enable_compile_cache as e; print(e())"],
        capture_output=True, text=True, timeout=300,
        env=dict(env, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stderr[-2000:]
    want = tmp_path / env_dir if env_dir else _ROOT / ".jax_cache"
    assert r.stdout.strip() == str(want)


def test_chip_smoke_refuses_cpu():
    """chip_smoke.py exits non-zero before any work when JAX finds no TPU,
    names the platform it found, and prints no result line."""
    r = subprocess.run([sys.executable, str(_ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300,
                       env=dict(_ENV, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr, r.stderr[-2000:]
    assert r.stdout == "", r.stdout[-2000:]
