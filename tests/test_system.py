"""End-to-end behaviour tests: the launchers and the paper's headline
phenomena on small problems."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_logreg_problem
from repro.core import (
    PiscoConfig,
    make_sparse_topology,
    replicate_params,
    run_training,
    sparse_mixing,
)
from repro.utils.compile_cache import enable_compile_cache


REPO = Path(__file__).resolve().parents[1]


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # the launchers turn on the persistent compile cache; tests keep it off
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return env


def test_compile_cache_honours_env_var(monkeypatch, tmp_path):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_dir_in_checkout(monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        placed = enable_compile_cache()
        assert placed == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == placed
        assert enable_compile_cache() == placed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_refuses_without_tpu(tmp_path, where):
    script = REPO / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = _env()
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(script)], cwd=script.parent, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "FAIL" in proc.stderr


@pytest.mark.slow
def test_train_launcher_end_to_end():
    proc = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.train",
            "--arch", "qwen3-8b", "--reduced", "--rounds", "4",
            "--n-agents", "4", "--t-o", "1", "--batch", "2", "--seq", "32",
            "--log-every", "1",
        ],
        env=_env(), capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "done: 4 rounds" in proc.stdout
    assert "loss=" in proc.stdout


@pytest.mark.slow
def test_serve_launcher_end_to_end():
    proc = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.serve",
            "--arch", "mamba2-370m", "--reduced", "--agents", "4",
            "--slots", "2", "--requests", "3",
            "--prompt-len", "16", "--gen", "4",
            "--fixed-costs", "0.05,0.01",
        ],
        env=_env(), capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "tok/s" in proc.stdout
    assert "latency p50=" in proc.stdout
    assert "fleet: synthetic (4 agents" in proc.stdout


def test_small_p_approaches_full_server_performance():
    """Fig. 5 phenomenon: p=0.1 performs close to p=1 in rounds-to-threshold."""
    n = 8
    loss_fn, full_grad_sq, sampler_factory, d = make_logreg_problem(n_agents=n)
    mixing = sparse_mixing(make_sparse_topology("ring", n))
    x0 = replicate_params({"w": jnp.zeros(d)}, n)
    rounds = {}
    for p in (0.0, 0.1, 1.0):
        cfg = PiscoConfig(n_agents=n, t_o=4, eta_l=0.15, eta_c=1.0, p=p, seed=2)
        hist = run_training(
            "pisco", loss_fn, x0, cfg, mixing, sampler_factory(4),
            rounds=70,
            eval_fn=lambda xb: {"grad_sq": full_grad_sq(xb)},
            eval_every=1,
        )
        r = hist.rounds_to_threshold("grad_sq", 0.05)
        rounds[p] = r if r is not None else 10_000
    assert rounds[0.1] <= rounds[0.0]
    assert rounds[0.1] <= max(2 * rounds[1.0], rounds[1.0] + 15)


@pytest.mark.slow
def test_checkpoint_resume_in_train_launcher(tmp_path):
    args = [
        sys.executable, "-m", "repro.launch.train",
        "--arch", "mamba2-370m", "--reduced",
        "--n-agents", "2", "--t-o", "1", "--batch", "2", "--seq", "32",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
    ]
    proc = subprocess.run(
        args + ["--rounds", "3"],
        env=_env(), capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    files = os.listdir(tmp_path)
    assert any(f.startswith("ckpt_") for f in files)
    # resume: the second invocation restores the snapshot state and only
    # runs the remaining rounds
    proc = subprocess.run(
        args + ["--rounds", "4", "--log-every", "1"],
        env=_env(), capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "restored" in proc.stdout
    assert "round    3" in proc.stdout
    assert "round    0" not in proc.stdout  # starts at the restored round
