"""The command itself, rehearsed on the CPU: with no TPU it exits non-zero
and prints no result line, and a checkout holding only the benchmark's
files cannot run at all."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _run(cwd, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "metrics" in obj), line


@pytest.mark.parametrize("workload", CELLS)
def test_no_chip_no_result(workload):
    proc = _run(ROOT, workload)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "no TPU" in proc.stderr
    _no_result(proc)


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax-cache", "__pycache__"))
    env_path = os.environ.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu",
                              PYTHONPATH=env_path.replace("src", "")))
    assert proc.returncode != 0
    _no_result(proc)
