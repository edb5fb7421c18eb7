"""The compiled pipeline's share of its HBM roofline: the least time the
chip could take for one query — the query's logical bytes (the source
columns it reads, once, plus its output; `logical_bytes` of the
configuration) at the chip's peak HBM bandwidth — over the device time one
query took in the traced window.  Bound by bytes: the query does a few
arithmetic operations per row."""


def read(ctx):
    t, c = ctx.trace, ctx.outcome.counters
    if not t or not c.get("queries") or "rows_out" not in c:
        return None
    busy = t["busy_s"][t["busiest"]] / c["queries"]
    if busy <= 0:
        return None
    least = ctx.run.flows.logical_bytes(ctx.run.config, c["rows_out"]) \
        / ctx.peaks["hbm_bytes_per_s"]
    return least / busy * 100.0
