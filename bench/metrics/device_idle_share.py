"""Share of the traced window in which no operation ran on the device,
averaged over the chips the cell uses: 1 - union(op intervals) / window."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.mean_busy_s() / t.window_s)
