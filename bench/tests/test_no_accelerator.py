"""Without an accelerator, or without the program, a run exits non-zero
and prints no result."""

import os
import shutil
import subprocess
import sys

from bench.tests import tiny

ARGS = ["--workload", "pruned_csc.backlog", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(root, env):
    return subprocess.run([sys.executable, os.path.join(root, "bench",
                                                        "run.py")] + ARGS,
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_accelerator_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(tiny.ROOT, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_the_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(tiny.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run(str(tmp_path), env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "system under test is missing" in p.stderr
