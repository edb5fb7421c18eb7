"""The mesh cell at a tiny size in a child process with four host devices
(the device count is fixed when JAX starts): correct with no compilation in
the window, and not correct with the exchange between chips left out or an
answer altered where it is produced."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from chipbench.tests._util import ROOT


_MESH = """
import json, sys
sys.path[:0] = [%(root)r, %(src)r]
from chipbench.tests import _util as U
fault = %(fault)r
if fault == "exchange":
    from repro.core import distributed as DX
    DX._repartition = lambda b, keys, axis, p, slices=1: b
    DX._broadcast = lambda b, axis, p, slices=1: b
elif fault == "altered":
    from repro.core.distributed import DistributedPlan
    run_device = DistributedPlan.run_device
    DistributedPlan.run_device = lambda self, *a, **k: U.altered(
        run_device(self, *a, **k))
r = U.tiny_run(U.MESH_CELL)
out = U.drive(r)
print("MESH " + json.dumps({"correct": U.correct(out),
                            "compiles": r.window_compiles,
                            "counters": out.counters}))
"""


@pytest.mark.parametrize("fault", [None, "exchange", "altered"])
def test_mesh_cell_on_four_host_devices(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _MESH % {"root": ROOT, "src": os.path.join(ROOT, "src"),
                    "fault": fault}
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    line = next(ln for ln in p.stdout.splitlines() if ln.startswith("MESH "))
    res = json.loads(line[5:])
    assert res["counters"]["mesh_width"] == 4
    if fault is None:
        assert res["correct"] and res["compiles"] == 0
        assert res["counters"]["wire_bytes_per_query"] > 0
    else:
        assert not res["correct"]
