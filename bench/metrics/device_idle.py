"""Share of the window in which no operation ran on the device (%),
averaged over the cell's chips: 1 - union of op intervals / window."""


def read(ctx):
    w = ctx.window_s()
    return 100.0 * (1.0 - ctx.busy_s() / w) if w > 0 else None
