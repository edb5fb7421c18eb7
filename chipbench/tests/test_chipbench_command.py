"""The command refuses to run without a TPU (here, on the CPU), and in a
directory that holds only `BENCHMARK.json` and the benchmark's files;
it prints no result line either way."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

from chipbench.tests._util import ROOT


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "q15-sf1-pipeline",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=cwd, env=env)


@pytest.mark.parametrize("where", ["checkout", "benchmark_only"])
def test_refuses_without_a_tpu(where, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    cwd = ROOT
    if where == "benchmark_only":
        cwd = tmp_path
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
        shutil.copytree(os.path.join(ROOT, "chipbench"),
                        tmp_path / "chipbench",
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(cwd, env)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "no TPU" in r.stderr
