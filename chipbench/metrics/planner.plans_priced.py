"""Physical alternatives the planner prices in set-up: the program's
`optimize.priced` counter while the cell's flow is planned once more, under
the context its path (`paths/<path>.py`) plans with.  Planning is
deterministic, so this is the count set-up made.  A program without the
counter reads nothing."""


def read(ctx):
    from repro import obs
    from repro.core.optimizer import optimize
    from repro.core.physical import Ctx

    r = ctx.run
    chips = int(r.workload["chips"])
    flow = r.flows.flow(r.config)
    obs.reset()
    obs.enable()
    try:
        optimize(flow, Ctx(dop=chips) if chips > 1 else Ctx())
    finally:
        obs.disable()
        counts = obs.snapshot()["counts"]
        obs.reset()
    return counts.get("optimize.priced") or None
