"""``run.py --selftest``: every cell, admitted or candidate, both modes, at
toy sizes on the CPU. Several minutes (each run compiles its programs for
the CPU). And: off a TPU the real command refuses, with nothing on standard
output."""

import os
import subprocess
import sys

from benchmark import plugins

RUN = os.path.join(plugins.HERE, "run.py")


def test_off_a_tpu_the_command_refuses_and_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax"))
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", "gbm64-higgs-build", "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=plugins.ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs 1 tpu device" in proc.stderr


def test_without_the_program_the_command_refuses(tmp_path):
    """A directory that holds only BENCHMARK.json and benchmark/."""
    import shutil
    shutil.copytree(plugins.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(plugins.ROOT, "BENCHMARK.json"), tmp_path)
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"), "--selftest",
         "--workload", "gbm64-higgs-build", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_selftest_runs_every_cell(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax"))
    proc = subprocess.run([sys.executable, RUN, "--selftest"],
                          cwd=plugins.ROOT, env=env, capture_output=True,
                          text=True, timeout=3000)
    assert proc.returncode == 0, proc.stdout + proc.stderr[-3000:]
    assert "FAILED" not in proc.stdout
    assert proc.stdout.strip().endswith("not a result")
