"""Time per round between two runs of the round program (ms), averaged
over the chips: at each chunk boundary the host fetches the chunk's
history, plans the next chunk (handover planning runs small eager
programs on the device), stacks its schedule and dispatches it. The
stretch before the window's first chunk (the data-stack build and
upload) is left out; it shows in `breakdown`."""


def read(ctx):
    devs = ctx.trace["devices"]
    tot, seen = 0, False
    for d in devs:
        spans = sorted(ctx.tr.round_module(ctx.trace, d))
        seen = seen or len(spans) > 1
        tot += sum(b[0] - a[1] for a, b in zip(spans, spans[1:]))
    return tot / len(devs) / ctx.rounds / 1e6 if seen else None
