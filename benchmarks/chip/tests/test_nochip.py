"""No chip, no numbers: the command fails and prints no result without a
TPU, and without the program beside the benchmark."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
REPO = CHIP.parents[1]
ARGS = ["--workload", "cascade-chat", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def run(root: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(root / "benchmarks/chip/run.py"), *ARGS],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def no_result(p) -> bool:
    return p.returncode != 0 and not any(
        line.startswith("{") for line in p.stdout.splitlines())


def test_exits_nonzero_without_a_tpu():
    p = run(REPO)
    assert no_result(p), p.stdout + p.stderr
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks/chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path)
    assert no_result(p), p.stdout + p.stderr
