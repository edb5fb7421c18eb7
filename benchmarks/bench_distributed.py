"""Weak-scaling sweep of the sharded executor on an 8-way CPU host mesh.

One subprocess (forced 8 host devices pinned to the CPU platform, same
isolation as bench_aggregation: the child never contends for an accelerator
the parent holds) serves a 6-aggregate Reduce flow at a FIXED 8192 rows per shard while the
mesh widens 1 -> 2 -> 4 -> 8, once with the sliced overlap wire
(`overlap_slices=4`, the default) and once with the serial per-column wire
(`overlap_slices=1`, the `REPRO_OVERLAP=0` path).  Reported per width:

    mesh_bps / t_overlap_ms / t_serial_ms
        — warm `DistributedPlan.run_device` rate (median of interleaved
          on/off trials, so host drift hits both paths equally);
    eff_overlap / eff_serial
        — throughput-normalized weak-scaling efficiency
          (p * t(1 shard)) / t(p shards): the fraction of perfect scaling
          retained as the mesh widens.  A within-run ratio, so it is
          machine-independent even though absolute rates are not;
    wire_rows / wire_bytes / dispatches / overlap_fraction
        — `distributed.shuffle_stats` collective accounting (trace-time),
          wire_bytes being the §12 comms-model validation hook against
          `cost.wire_profile`.

The sliced and serial wires are asserted BYTE-identical before any timing.
On this emulated mesh every "device" is a host thread, so its numbers
are counts and within-run ratios, not chip speed: collective latency
cannot genuinely hide under compute; the overlap path's measured
edge comes from issuing K packed collectives instead of one per column
(dispatch_reduction in the summary).  check_regression.py gates
`weak_scaling_efficiency` >= BENCH_MIN_WEAK_SCALING (default 0.6) in both
artifacts, strict overlap-beats-serial efficiency on the committed
baseline, and the schedule superiority (dispatch_reduction > 1, nonzero
overlap fraction) everywhere.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.core import flow as F
from repro.core.operators import Hints
from repro.core.record import Schema, batch_from_dict

ROWS_PER_SHARD = 8192
N_VALS = 6            # aggregate columns: serial wire = one op per column
N_GROUPS = 512
MESH = 8
OVERLAP = 4
SHARDS_FULL = (1, 2, 4, 8)
SHARDS_QUICK = (1, 8)

_FIELDS = {f"v{i}": np.int64 for i in range(N_VALS)}
_SCHEMA = Schema.of(a=np.int64, w=np.int64, **_FIELDS)


def scale_flow(rows: int):
    """Filter -> grouped 6-way sum; the combiner split keeps the shuffled
    edge narrow, the 6 aggregate columns make the serial wire chatty."""
    src = F.source("I", _SCHEMA, num_records=rows)

    def keep(ir, out):
        out.emit(ir.copy(), where=ir.get("w") > 0)

    m = F.map_(src, keep, name="Keep", hints=Hints(selectivity=0.5))

    def agg(g, out):
        o = g.keys()
        for i in range(N_VALS):
            o = o.set(f"s{i}", g.sum(f"v{i}"))
        out.emit(o)

    return F.reduce_(m, ["a"], agg, name="Agg",
                     hints=Hints(distinct_keys=N_GROUPS))


def bindings(rows: int, seed: int):
    rng = np.random.default_rng(seed)
    d = {"a": rng.integers(0, N_GROUPS, rows),
         "w": rng.integers(-5, 5, rows)}
    for i in range(N_VALS):
        d[f"v{i}"] = rng.integers(-99, 99, rows)
    return {"I": batch_from_dict(d)}


_MESH_SCRIPT = textwrap.dedent("""
    import os, sys, json, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
    sys.path.insert(0, %r)
    import numpy as np
    from benchmarks import bench_distributed as BD
    from repro.core import distributed as DX, executor
    from repro.core.cost import wire_profile
    from repro.core.optimizer import optimize
    from repro.core.physical import Ctx
    from repro.core.pipeline import ExecutableCache

    shards = %r
    reps = %d
    stats = DX.shuffle_stats()

    def timed(dp, staged):
        t0 = time.perf_counter()
        dp.run_device(staged).to_record_batch()
        return time.perf_counter() - t0

    rows_out, t1 = [], {}
    for p in shards:
        rows = BD.ROWS_PER_SHARD * p
        root = BD.scale_flow(rows)
        b = BD.bindings(rows, seed=7)
        res = optimize(root, Ctx(dop=p))
        plans = {}
        obs = {}
        for tag, k in (("overlap", BD.OVERLAP), ("serial", 1)):
            dp = DX.DistributedPlan(res.best.plan, mesh_shards=p,
                                    overlap_slices=k,
                                    cache=ExecutableCache())
            staged = dp.bind(b)
            stats.clear()
            out = dp.run_device(staged).to_record_batch()   # traces
            obs[tag] = {"wire_rows": stats.wire_rows,
                        "wire_bytes": stats.wire_bytes,
                        "dispatches": stats.dispatches,
                        "sites": stats.sites,
                        "overlap_fraction":
                            round(stats.overlap_fraction(), 4),
                        "out": out}
            for _ in range(2):
                dp.run_device(staged)                       # warm
            plans[tag] = (dp, staged)
        # sliced wire must be BYTE-identical to the serial wire
        o_on, o_off = obs["overlap"]["out"], obs["serial"]["out"]
        assert set(o_on.fields) == set(o_off.fields)
        for f in o_on.fields:
            a, c = np.asarray(o_on[f]), np.asarray(o_off[f])
            assert a.shape == c.shape, (p, f)
            assert (a.view(np.uint8) == c.view(np.uint8)).all(), (p, f)
        ref = executor.execute(root, b)
        assert o_on.equivalent(ref, atol=0), p

        ts = {"overlap": [], "serial": []}
        for _ in range(reps):   # interleaved so host drift hits both
            ts["overlap"].append(timed(*plans["overlap"]))
            ts["serial"].append(timed(*plans["serial"]))
        med = {tag: sorted(v)[len(v) // 2] for tag, v in ts.items()}
        t1[("overlap", p)] = med["overlap"]
        t1[("serial", p)] = med["serial"]
        row = {"flow": "shards-%%d" %% p, "shards": p, "rows": rows,
               "t_overlap_ms": round(med["overlap"] * 1e3, 3),
               "t_serial_ms": round(med["serial"] * 1e3, 3),
               "mesh_bps": round(1.0 / med["overlap"], 2),
               "wire_rows": obs["overlap"]["wire_rows"],
               "wire_bytes": obs["overlap"]["wire_bytes"],
               "dispatches_overlap": obs["overlap"]["dispatches"],
               "dispatches_serial": obs["serial"]["dispatches"],
               "overlap_fraction": obs["overlap"]["overlap_fraction"]}
        rows_out.append(row)

    base_on = t1[("overlap", shards[0])] / shards[0]
    base_off = t1[("serial", shards[0])] / shards[0]
    for row in rows_out:
        p = row["shards"]
        row["eff_overlap"] = round(
            base_on * p / t1[("overlap", p)], 4)
        row["eff_serial"] = round(
            base_off * p / t1[("serial", p)], 4)

    # §12 comms-model validation at the full mesh width
    p = shards[-1]
    res = optimize(BD.scale_flow(BD.ROWS_PER_SHARD * p), Ctx(dop=p))
    model = wire_profile(res.best.plan, dop=p)
    model_rows = sum(e["rows"] for e in model)
    model_bytes = sum(e["bytes"] for e in model)
    print("DIST " + json.dumps({
        "rows": rows_out,
        "model_wire_rows": int(model_rows),
        "model_wire_bytes": int(model_bytes)}))
""")


def _mesh_sweep(shards, reps: int) -> dict:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src") + os.pathsep + repo \
        + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # the parent may hold the accelerator
    r = subprocess.run(
        [sys.executable, "-c",
         _MESH_SCRIPT % (MESH, repo, tuple(shards), reps)],
        capture_output=True, text=True, timeout=600, env=env, cwd=repo)
    if r.returncode != 0:  # pragma: no cover - surfaced in the summary
        raise RuntimeError(f"mesh subprocess failed: {r.stderr[-2000:]}")
    line = next(ln for ln in r.stdout.splitlines() if ln.startswith("DIST "))
    return json.loads(line[5:])


def run(quick: bool = False):
    shards = SHARDS_QUICK if quick else SHARDS_FULL
    sweep = _mesh_sweep(shards, reps=7 if quick else 11)
    rows = sweep["rows"]
    top = rows[-1]  # full mesh width

    from . import common

    common.print_rows("bench_distributed (weak scaling, 8-way host mesh)",
                      rows)
    print(f"weak-scaling efficiency @{top['shards']} shards: "
          f"overlap={top['eff_overlap']} serial={top['eff_serial']} "
          f"(overlap fraction {top['overlap_fraction']}, "
          f"{top['dispatches_serial']}/{top['dispatches_overlap']} "
          "dispatches serial/overlap)")
    return {
        "name": "distributed",
        "rows": rows,
        "rows_per_shard": ROWS_PER_SHARD,
        "weak_scaling_efficiency": top["eff_overlap"],
        "weak_scaling_efficiency_serial": top["eff_serial"],
        "overlap_fraction": top["overlap_fraction"],
        "dispatch_reduction": round(
            top["dispatches_serial"] / max(top["dispatches_overlap"], 1), 2),
        "wire_rows": top["wire_rows"],
        "wire_bytes": top["wire_bytes"],
        "model_wire_rows": sweep["model_wire_rows"],
        "model_wire_bytes": sweep["model_wire_bytes"],
        "bit_identical": True,
    }


if __name__ == "__main__":
    run()
