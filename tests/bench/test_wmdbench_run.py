"""`bench/run.py` refuses to report anything off a TPU, and outside a
checkout that holds the program."""
import os
import shutil
import subprocess
import sys

from wmdbench_testing import BENCH, REPO

ARGS = ["--workload", "paper_5k.full_bulk", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, script, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, script, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_refuses_a_cpu_device():
    p = _run(REPO, os.path.join("bench", "run.py"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), os.path.join("bench", "run.py"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "src/repro not found" in p.stderr


def test_refuses_an_unknown_workload():
    p = subprocess.run([sys.executable, os.path.join("bench", "run.py"),
                        "--workload", "nope", "--seed", "1", "--seconds",
                        "1"], cwd=REPO, capture_output=True, text=True,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
