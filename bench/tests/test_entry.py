"""The entry point refuses to run where it cannot measure: without a TPU,
and in a directory that holds the benchmark but not the program."""
import os
import shutil
import subprocess
import sys

from harness import spec

ARGS = ["--workload", "study.refine", "--seed", str(2 ** 31 + 7),
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(root, "bench",
                                                        "run.py"), *ARGS],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = _run(spec.ROOT)
    assert p.returncode == 3 and p.stdout == ""
    assert "TPU" in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0 and p.stdout == ""
