"""The command exits with no result where it cannot measure: on a host
with no accelerator, and in a directory that holds only the benchmark."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "t3large.fresh", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def run_bench(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(proc) -> bool:
    for line in proc.stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_no_accelerator_no_result():
    proc = run_bench(ROOT)
    assert proc.returncode != 0
    assert no_result(proc)
    assert "no result" in proc.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_bench(tmp_path)
    assert proc.returncode != 0
    assert no_result(proc)
