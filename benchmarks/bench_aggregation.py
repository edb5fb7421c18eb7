"""Decomposable-aggregation push-down: shuffled-row reduction + serving rate.

The split-Reduce rewrite's payoff is network volume: the combiner runs per
worker BEFORE the repartition collective, so only ~groups·p narrow partial
records cross the wire instead of every input row.  This benchmark measures,
on a Reduce-after-shuffle flow with 64 groups over 8192 rows (the acceptance
shape) and on its PK-join eager-aggregation variant:

    shuffled_rows_unsplit / shuffled_rows_split
        — VALID rows entering the repartition boundary (eager row accounting
          of the pre-shuffle subtree), reported as `reduction_factor`;
    wire ratio on 8 forced host devices
        — actual all_to_all buffer slots (`distributed.shuffle_stats`),
          measured in a subprocess pinned to the CPU platform so the forced
          device count cannot leak and the child never contends for an
          accelerator the parent holds.  An emulated 8-shard host mesh:
          these are counts, not chip speed;
    pipeline_bps
        — warm compiled-pipeline batches/sec of the chosen (split) plan.

`combiner_inserted` asserts the optimizer actually picks the split plan.
benchmarks/check_regression.py gates CI on `reduction_factor` >= 3x and on
its quick-vs-baseline stability.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

from repro.core import executor, flow as F
from repro.core.operators import Hints, ReduceOp
from repro.core.optimizer import optimize
from repro.core.physical import Ctx
from repro.core.pipeline import ExecutableCache, compile_plan
from repro.core.record import Schema, batch_from_dict

N_ROWS, N_GROUPS, DOP = 8192, 64, 8

_SCHEMA = Schema.of(k=np.int64, v=np.int64, w=np.float64)


def _agg_udf():
    def agg(g, out):
        out.emit(g.keys().set("s", g.sum("v")).set("avg", g.mean("w")))

    return agg


def reduce_flow():
    src = F.source("I", _SCHEMA, num_records=N_ROWS)
    return F.reduce_(src, ["k"], _agg_udf(), name="Agg",
                     hints=Hints(distinct_keys=N_GROUPS))


def join_flow():
    src = F.source("I", _SCHEMA, num_records=N_ROWS)
    dim = F.source("Dim", Schema.of(dk=np.int64, dv=np.int64),
                   num_records=N_GROUPS)
    j = F.match(src, dim, ["k"], ["dk"], name="J",
                hints=Hints(pk_side="right"))
    return F.reduce_(j, ["k"], _agg_udf(), name="Agg",
                     hints=Hints(distinct_keys=N_GROUPS))


def bindings(seed=0):
    rng = np.random.default_rng(seed)
    out = {"I": batch_from_dict({"k": rng.integers(0, N_GROUPS, N_ROWS),
                                 "v": rng.integers(-100, 100, N_ROWS),
                                 "w": rng.uniform(0, 1, N_ROWS)})}
    out["Dim"] = batch_from_dict({"dk": np.arange(N_GROUPS),
                                  "dv": np.arange(N_GROUPS) * 3})
    return out


def _partition_input_rows(plan, b) -> int:
    """VALID rows crossing the first partition-shipped edge of `plan`:
    eager row count of the sub-plan feeding that repartition."""
    stack = [plan]
    while stack:
        p = stack.pop()
        for ship, inp in zip(p.ship, p.inputs):
            if ship == "partition":
                return executor.execute(inp.node, b).num_valid()
            stack.append(inp)
    return 0


_WIRE_SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
    sys.path.insert(0, %r)
    import numpy as np
    from benchmarks import bench_aggregation as BA
    from repro.core import distributed as DX, executor
    from repro.core.optimizer import optimize
    from repro.core.physical import Ctx

    root = BA.reduce_flow()
    b = BA.bindings(11)
    ref = executor.execute(root, b)
    res = optimize(root, Ctx(dop=%d))
    stats = DX.shuffle_stats()
    out = {}
    for tag, rp in (("split", res.best),
                    ("unsplit", next(r for r in res.ranked
                                     if ".pre" not in r.order()))):
        stats.clear()
        got = DX.execute_distributed(rp.plan, b)
        assert got.equivalent(ref, atol=1e-4), tag
        out[tag] = stats.wire_rows
    out["chosen"] = res.best.order()
    print("WIRE " + json.dumps(out))
""")


def _wire_rows() -> dict:
    """all_to_all buffer-slot accounting on DOP forced host devices."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep + repo \
        + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # the parent may hold the accelerator
    r = subprocess.run(
        [sys.executable, "-c", _WIRE_SCRIPT % (DOP, repo, DOP)],
        capture_output=True, text=True, timeout=600, env=env, cwd=repo)
    if r.returncode != 0:  # pragma: no cover - surfaced in the summary
        raise RuntimeError(f"wire subprocess failed: {r.stderr[-2000:]}")
    line = next(ln for ln in r.stdout.splitlines() if ln.startswith("WIRE "))
    return json.loads(line[5:])


def _pipeline_bps(plan_flow, b, repeats: int) -> float:
    cp = compile_plan(plan_flow, cache=ExecutableCache())
    cp.run(b)  # cold
    t0 = time.perf_counter()
    for _ in range(repeats):
        cp.run(b)
    return repeats / (time.perf_counter() - t0)


def _bench_case(name: str, root, b, ctx: Ctx, repeats: int) -> dict:
    ref = executor.execute(root, b)
    res = optimize(root, ctx)
    best = res.best
    combiner = any(isinstance(n, ReduceOp) and n.combiner
                   for n in best.flow.iter_nodes())
    got = executor.execute(best.flow, b)
    assert got.equivalent(ref, atol=1e-4), name

    unsplit = next(rp for rp in res.ranked if ".pre" not in rp.order())
    rows_split = _partition_input_rows(best.plan, b)
    rows_unsplit = _partition_input_rows(unsplit.plan, b)
    reduction = rows_unsplit / max(rows_split, 1)
    return {
        "flow": name,
        "rows": N_ROWS,
        "groups": N_GROUPS,
        "dop": ctx.dop,
        "combiner_inserted": bool(combiner),
        "shuffled_rows_unsplit": int(rows_unsplit),
        "shuffled_rows_split": int(rows_split),
        "reduction_factor": round(reduction, 1),
        "pipeline_bps": round(_pipeline_bps(best.flow, b, repeats), 2),
        "chosen": best.order(),
    }


def run(quick: bool = False):
    ctx = Ctx(dop=DOP)
    b = bindings(7)
    repeats = 5 if quick else 25

    rows = [_bench_case("agg-shuffle", reduce_flow(), b, ctx, repeats),
            _bench_case("agg-below-join", join_flow(), b, ctx, repeats)]

    wire = _wire_rows()
    wire_ratio = wire["unsplit"] / max(wire["split"], 1)

    from . import common

    common.print_rows("bench_aggregation (decomposable push-down)", rows)
    print(f"wire rows over {DOP} workers: unsplit={wire['unsplit']} "
          f"split={wire['split']} ({wire_ratio:.1f}x fewer)")
    return {"name": "aggregation",
            "wire_rows_unsplit": int(wire["unsplit"]),
            "wire_rows_split": int(wire["split"]),
            "wire_reduction_factor": round(wire_ratio, 1),
            "rows": rows}


if __name__ == "__main__":
    run()
