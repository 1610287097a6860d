"""The command as the driver runs it, where it must refuse to run."""

import os
import shutil
import subprocess
import sys

from benchmark.harness import manifest

ARGS = ["--workload", "train-large-steady", "--seed", "2147483999", "--seconds", "1", "--trace", "0"]


def run(cwd: str):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_no_chip_no_result():
    proc = run(manifest.ROOT)
    assert proc.returncode not in (0, None)
    assert "needs 1 TPU chip" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_bare_directory_no_result(tmp_path):
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(manifest.ROOT, "benchmark"), tmp_path / "benchmark",
        ignore=shutil.ignore_patterns(".run", "__pycache__"),
    )
    proc = run(str(tmp_path))
    assert proc.returncode not in (0, None)
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
