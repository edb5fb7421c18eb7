"""Share of the busiest chip's busy time during which a collective
operation (all-gather, all-to-all, all-reduce and the like, synchronous or
in flight asynchronously) is in progress, in the traced window."""


def read(ctx):
    t = ctx.trace
    if not t:
        return None
    dev = t["busiest"]
    busy, coll = t["busy_s"][dev], t["collective_s"][dev]
    if busy <= 0 or coll <= 0:   # no collective found: nothing to read
        return None
    return coll / busy * 100.0
