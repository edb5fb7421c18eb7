"""Driver: one flow through the compiled pipeline, back to back on one
device-resident batch — `optimize(flow).compile()` -> `bind_device` ->
`run_device`."""

from __future__ import annotations

import jax

from chipbench import compare as C
from chipbench import loop
from chipbench.harness import Outcome


def run(r) -> Outcome:
    from repro.core.optimizer import optimize
    from repro.core.pipeline import ExecutableCache
    from repro.core.record import batch_from_dict

    cfg = r.config
    data = r.flows.generate(cfg, r.seed)
    with r.span("plan"):
        res = optimize(r.flows.flow(cfg))
    cache = ExecutableCache()
    cp = res.compile(cache=cache)
    with r.span("bind"):
        staged = jax.block_until_ready(cp.bind_device(
            {name: batch_from_dict(cols) for name, cols in data.items()}))
    with r.span("warmup"):
        for _ in range(2):
            jax.block_until_ready(cp.run_device(staged))
    done, seconds, outs = loop.back_to_back(
        r, lambda: cp.run_device(staged), r.traffic["in_flight"], [cache])
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
                  "plan": res.best.order(), "control": r.control},
        attempted=done, failed=0,
        checks={"rows_mismatched": (max(c[0] for c in checks),
                                    lim["rows_mismatched"]),
                "rel_err": (max(c[1] for c in checks), lim["rel_err"])})
