"""``run.py`` prints no result and exits non-zero off a TPU, and in a
directory that holds only ``BENCHMARK.json`` and the benchmark's files."""
import os
import shutil
import subprocess
import sys

import _chipbench_tiny as tiny

ARGS = ["--workload", "fleet.paper-mlp.ring4096", "--seed", "4294967311", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env):
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(tiny.REPO / "src"))
    r = _run(tiny.REPO, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "not a TPU" in r.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path)
    for p in ("benchmarks/chip", "tests/bench"):
        shutil.copytree(tiny.REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = _run(tmp_path, dict(env, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no program" in r.stderr
