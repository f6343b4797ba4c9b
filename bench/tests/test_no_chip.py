"""The command refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "internlm2.chat", "--seed", str(2 ** 31 + 1),
        "--seconds", "1", "--trace", "0"]


def run(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(p):
    return not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_exits_nonzero_with_no_result_where_jax_finds_no_tpu():
    p = run(ROOT)
    assert p.returncode != 0 and no_result(p)
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path)
    assert p.returncode != 0 and no_result(p)
