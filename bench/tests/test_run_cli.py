"""The command prints no result and exits non-zero where it cannot measure:
without the system under test, or without an accelerator."""
import os
import shutil
import subprocess
import sys

from bench import harness

ARGS = ["--workload", "table1.sim-msweep", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_accelerator_no_result():
    r = _run(harness.ROOT)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "no accelerator" in r.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copytree(os.path.join(harness.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    r = _run(str(tmp_path))
    assert r.returncode != 0
    assert r.stdout == ""
