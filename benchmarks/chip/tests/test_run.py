"""``run.py`` without a TPU, and in a directory without the program."""
import os
import shutil
import subprocess
import sys

from benchmarks.chip import harness

ARGS = ["--workload", "smollm-360m.chat-sampling", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def launch(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *ARGS], cwd=root, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    p = launch(harness.CHECKOUT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_without_the_program_it_fails(tmp_path):
    shutil.copy(harness.CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = launch(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
