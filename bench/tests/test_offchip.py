"""A measuring run refuses to run without a TPU, and without the program."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

ARGS = ["--workload", "lowrank.backlog-131k", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)


def test_off_a_tpu_exits_nonzero_with_no_result():
    out = _run(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""
