"""The command refuses to run without the chip, and without the program."""
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

ARGS = ["--workload", "qwen3-1.7b.chat", "--seed", str(2**33),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
