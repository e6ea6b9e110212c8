"""Device time of collective ops per round (ms), averaged over the
chips: the exchange of the cohort-sharded round (mesh reduction and the
gathers around it)."""


def read(ctx):
    devs = ctx.trace["devices"]
    tot = sum(ctx.tr.class_ns(ctx.tr.in_window(ctx.trace, d), "collective")
              for d in devs)
    return tot / len(devs) / ctx.rounds / 1e6 if tot else None
