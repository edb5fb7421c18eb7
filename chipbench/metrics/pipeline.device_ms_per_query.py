"""Device milliseconds per query: the busiest chip's busy time in the
traced window (the union of its operations) over the queries the window
completed."""


def read(ctx):
    t, n = ctx.trace, ctx.outcome.counters.get("queries")
    if not t or not n:
        return None
    return t["busy_s"][t["busiest"]] / n * 1e3
