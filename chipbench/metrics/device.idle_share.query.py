"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses."""


def read(ctx):
    t = ctx.trace
    if not t or t["window_s"] <= 0:
        return None
    return (1.0 - t["busy_mean_s"] / t["window_s"]) * 100.0
