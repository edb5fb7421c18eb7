"""Planner seconds in set-up: the host clock around `optimize`."""


def read(ctx):
    return ctx.run.spans.get("plan")
