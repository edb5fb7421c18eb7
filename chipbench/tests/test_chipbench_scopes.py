"""The cell's Q15 compiled at a tiny size names its device work by plan
stage and mechanism: in the optimized HLO every loop, sort and gather sits
under a `stage.` scope, the compaction loops under `compact` and the sorts
under `sort`, on the composed route and the megakernel route alike; on four
host devices the mesh program's collectives sit under `wire`.  XLA may drop
a scope from some instructions (on the CPU a `cumsum`'s reduce-window loses
its name stack), so only the operations that carry the time are checked."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

from chipbench.tests._util import ROOT

INSTR = re.compile(r"\s*(?:ROOT )?%\S+ = .*? ([\w-]+)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def ops_by_kind(hlo: str, kinds) -> list:
    """`(opcode, op_name)` of each instruction of `kinds` in HLO text."""
    out = []
    for line in hlo.splitlines():
        m = INSTR.match(line)
        if m and m.group(1) in kinds:
            s = OP_NAME.search(line)
            out.append((m.group(1), s.group(1) if s else ""))
    return out


def _q15_hlo(megakernel: bool):
    from chipbench.tests._util import tiny_run

    from repro.core.optimizer import optimize
    from repro.core.pipeline import ExecutableCache
    from repro.core.record import batch_from_dict

    r = tiny_run("q15-sf1-pipeline")
    cp = optimize(r.flows.flow(r.config)).compile(cache=ExecutableCache())
    cp.use_megakernel = megakernel
    cp.__post_init__()
    data = r.flows.generate(r.config, r.seed)
    staged = cp.bind_device({n: batch_from_dict(c) for n, c in data.items()})
    masked, sig = cp._masked_sig(staged)
    fn = cp._executable(sig)
    return cp, fn.lower(masked).compile().as_text()


@pytest.mark.parametrize("megakernel", [False, True])
def test_q15_stage_and_mechanism_scopes(megakernel):
    cp, hlo = _q15_hlo(megakernel)
    routes = cp._last_routes
    assert (routes is not None) == megakernel
    stages = {f"stage.{st.kind}.{st.top.name}" for st in cp.stages}
    ops = ops_by_kind(hlo, ("while", "sort", "gather"))
    assert {k for k, _ in ops} == {"while", "sort", "gather"}
    for kind, path in ops:
        found = [p for p in path.split("/") if p.startswith("stage.")]
        assert found and found[0] in stages, (kind, path)
    # the filter's compaction loop, and every sort, by mechanism
    assert any(k == "while" and "stage.chain.FilterShipdate/compact/" in p
               for k, p in ops)
    for kind, path in ops:
        if kind == "sort":
            assert "/sort/" in path, path
        if kind == "while" and "jit(searchsorted)" in path \
                and "/compact/" not in path:
            # searches outside a compaction: the PK probe, or the
            # aggregate's own segment search
            assert "/probe/" in path or "stage.reduce." in path, path


_MESH = """
import json, sys
sys.path[:0] = [%(root)r, %(src)r]
from chipbench.tests import _util as U
from chipbench.tests.test_chipbench_scopes import ops_by_kind
import jax
import numpy as np
from jax.sharding import Mesh
from repro.core import distributed as DX
from repro.core.optimizer import optimize
from repro.core.physical import Ctx
from repro.core.record import batch_from_dict
r = U.tiny_run(U.MESH_CELL)
data = r.flows.generate(r.config, r.seed)
res = optimize(r.flows.flow(r.config), Ctx(dop=4))
dp = DX.DistributedPlan(res.best.plan, mesh=Mesh(np.array(r.devices), ("data",)))
staged = dp.bind({n: batch_from_dict(c) for n, c in data.items()})
fn = dp._executable(staged, False)
hlo = fn.lower(*[staged[n] for n in sorted(staged)]).compile().as_text()
kinds = ("all-gather", "all-to-all", "all-reduce", "all-gather-start",
         "all-reduce-start", "collective-permute")
print("MESH " + json.dumps(ops_by_kind(hlo, kinds)))
"""


def test_mesh_collectives_under_wire():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _MESH % {"root": ROOT, "src": os.path.join(ROOT, "src")}
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    line = next(ln for ln in p.stdout.splitlines() if ln.startswith("MESH "))
    ops = json.loads(line[5:])
    assert ops
    for kind, path in ops:
        assert "/wire/" in path or path.endswith("/wire"), (kind, path)
