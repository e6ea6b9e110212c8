"""Device time per round of the client step (ms): the round program's
ops that are neither Pallas kernels nor collectives (motion blur, the
ResNet passes, BatchNorm, the DT loss, SGD), averaged over the chips."""


def read(ctx):
    devs = ctx.trace["devices"]
    tot = sum(ctx.tr.class_ns(ctx.round_ops(d), "conv", "xla") for d in devs)
    return tot / len(devs) / ctx.rounds / 1e6 if tot else None
