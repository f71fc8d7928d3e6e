"""``bench/run.py`` exits non-zero and prints no result where it cannot
run the cell: with no TPU, and in a directory that holds only
``BENCHMARK.json`` and the benchmark's own files."""

import shutil
import subprocess
import sys

import tiny

ARGS = ["--workload", tiny.CELL, "--seed", str(2 ** 31 + 5), "--seconds",
        "1", "--trace", "0"]


def run_in(root):
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(root)}
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = run_in(tiny.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_files_alone(tmp_path):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_in(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
