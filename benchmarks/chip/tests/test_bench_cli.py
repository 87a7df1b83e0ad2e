"""The command refuses to run without a TPU, and without the program."""

import json
import os
import shutil
import subprocess
import sys

import bench_tiny

RUN = os.path.join(bench_tiny.BENCH, "run.py")
ARGS = ["--workload", "granite-3-2b.chat", "--seed", "4294967311",
        "--seconds", "1", "--trace", "0"]


def run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def no_result(p):
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")


def test_exits_nonzero_without_tpu():
    p = run(bench_tiny.ROOT, RUN)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    no_result(p)


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(bench_tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bench_tiny.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path, str(tmp_path / "benchmarks" / "chip" / "run.py"))
    assert p.returncode != 0
    assert "no program" in p.stderr
    no_result(p)
