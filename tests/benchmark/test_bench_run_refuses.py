"""`run.py` prints a result line only on the chips the cell asks for."""

import os
import shutil
import subprocess
import sys

import bench_paths


def _run(cwd, *args):
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), *args],
        cwd=cwd, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)


def _no_result_line(stdout: str) -> bool:
    return not any(line.startswith("{") and '"correct"' in line for line in stdout.splitlines())


def test_run_refuses_a_cpu():
    r = _run(bench_paths.REPO, "--workload", "train-0.5b-gsm8k", "--seed", "3000000019",
             "--seconds", "1", "--trace", "0")
    assert r.returncode != 0 and _no_result_line(r.stdout)
    assert "needs 1 TPU chip" in r.stderr


def test_run_fails_where_only_the_benchmark_is(tmp_path):
    shutil.copy(os.path.join(bench_paths.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(bench_paths.REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(str(tmp_path), "--workload", "train-0.5b-gsm8k", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert r.returncode != 0 and _no_result_line(r.stdout)
    assert "areal_tpu" in r.stderr


def test_unknown_cell_is_an_error():
    r = _run(bench_paths.REPO, "--workload", "no-such-cell", "--seed", "1", "--seconds", "1")
    assert r.returncode != 0 and _no_result_line(r.stdout)
