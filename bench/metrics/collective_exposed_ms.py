"""The part of the collectives' device time per round (ms) during which
no other op runs on that chip, averaged over the chips."""
import bisect


def read(ctx):
    tr = ctx.tr
    devs = ctx.trace["devices"]
    tot, seen = 0, False
    for d in devs:
        ops = tr.in_window(ctx.trace, d)
        coll = tr.union((o[1], o[1] + o[2]) for o in ops
                        if o[3] == "collective")
        busy = tr.union((o[1], o[1] + o[2]) for o in ops
                        if o[3] != "collective")
        ends = [b[1] for b in busy]
        seen = seen or bool(coll)
        for s, e in coll:
            covered = 0
            for b0, b1 in busy[bisect.bisect_right(ends, s):]:
                if b0 >= e:
                    break
                covered += min(e, b1) - max(s, b0)
            tot += (e - s) - covered
    return tot / len(devs) / ctx.rounds / 1e6 if seen else None
