"""Bytes one query ships through the mesh's collectives, as the program's
`ShuffleStats.wire_bytes` counts them while the query's program traces."""


def read(ctx):
    return ctx.outcome.counters.get("wire_bytes_per_query") or None
