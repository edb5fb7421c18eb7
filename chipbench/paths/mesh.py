"""Driver: one flow across the cell's chips — `DistributedPlan` on a
one-axis mesh with repartition collectives, back to back on one bound
batch."""

from __future__ import annotations

import jax
import numpy as np

from chipbench import compare as C
from chipbench import loop
from chipbench.harness import Outcome


def run(r) -> Outcome:
    from jax.sharding import Mesh

    from repro.core import distributed as DX
    from repro.core.optimizer import optimize
    from repro.core.physical import Ctx
    from repro.core.pipeline import ExecutableCache
    from repro.core.record import batch_from_dict

    cfg = r.config
    chips = len(r.devices)
    data = r.flows.generate(cfg, r.seed)
    mesh = Mesh(np.array(r.devices), ("data",))
    with r.span("plan"):
        res = optimize(r.flows.flow(cfg), Ctx(dop=chips))
    cache = ExecutableCache()
    dp = DX.DistributedPlan(res.best.plan, mesh=mesh, cache=cache)
    with r.span("bind"):
        staged = jax.block_until_ready(dp.bind(
            {name: batch_from_dict(cols) for name, cols in data.items()}))
    wire = DX.shuffle_stats()
    wire.clear()
    with r.span("warmup"):
        for _ in range(2):
            out = jax.block_until_ready(dp.run_device(staged))
    # the wire is accounted while the program traces: once, for one query
    wire_bytes, dispatches = wire.wire_bytes, wire.dispatches
    placed = len(out.valid.sharding.device_set)
    del out
    done, seconds, outs = loop.back_to_back(
        r, lambda: dp.run_device(staged), r.traffic["in_flight"], [cache])
    got = r.answers(outs, data)
    del staged, outs
    ref = r.reference.reference(cfg, data)
    checks = [C.compare(g, ref) for g in got]
    lim = cfg["limits"]
    return Outcome(
        end_to_end={"rows_per_s": done * r.flows.rows_consumed(cfg)
                    / seconds},
        counters={"queries": done,
                  "rows_out": len(next(iter(ref.values()))),
                  "plan": res.best.order(), "control": r.control,
                  "mesh_width": dp.p,
                  "devices_placed": placed,
                  "wire_bytes_per_query": wire_bytes,
                  "dispatches_per_query": dispatches},
        attempted=done, failed=0,
        checks={"rows_mismatched": (max(c[0] for c in checks),
                                    lim["rows_mismatched"]),
                "rel_err": (max(c[1] for c in checks), lim["rel_err"]),
                "mesh_short": (chips - min(dp.p, placed), 0)})
