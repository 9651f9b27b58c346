"""The command's contract off the chip: no TPU, or no system under test
beside it, means a non-zero exit and no result line."""

import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

ARGS = ["--workload", "supernode_64sub_1m.gossip_slots", "--seed",
        "4294967297", "--seconds", "10", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_fails_and_prints_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_with_only_the_benchmark_files_it_fails_and_prints_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
