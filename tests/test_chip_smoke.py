"""`chip_smoke.py` on the CPU: it refuses to run without a TPU, and its
phase functions run here at a tiny size (the mesh phase on four forced host
devices, in a child process that never sees an accelerator)."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
sys.path.insert(0, REPO)

import chip_smoke as CS  # noqa: E402


def _cpu_env(**extra) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    return env


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_refuses_without_a_tpu(where, tmp_path):
    script, env = SCRIPT, _cpu_env()
    if where == "alone":   # the script without the program beside it
        script = shutil.copy(SCRIPT, tmp_path)
        env.pop("PYTHONPATH")
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, timeout=300, env=env, cwd=tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_pipeline_phase():
    rep = CS.phase_pipeline(rows=6000)
    assert rep["traces"] == 1 and rep["parity"] == "eager"
    assert rep["rows_out_seed1"] > 0


def test_engine_phase():
    rep = CS.phase_engine(rows=1024)
    assert rep["stats"]["coalesced_requests"] > 0
    assert rep["stats"]["solo_requests"] > 0
    assert rep["requests"] == 4 * CS.REQUESTS_PER_TENANT


_MESH = """
import json, sys
sys.path[:0] = [%r]
import chip_smoke as CS
rep = CS.phase_mesh(rows=24_000)
print("MESH " + json.dumps(rep))
"""


def test_mesh_phase_on_four_host_devices():
    env = _cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _MESH % REPO],
                       capture_output=True, text=True, timeout=600, env=env,
                       cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    line = next(ln for ln in r.stdout.splitlines() if ln.startswith("MESH "))
    assert '"devices": 4' in line and '"eager+one_chip"' in line
