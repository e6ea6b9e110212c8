"""Whole-step model FLOP utilisation (%): the training FLOPs a round
needs (flops/<model>.py: forward, weight and input gradients, no
recompute) times the rounds of the traced window, over the window's
length and the chips' bf16 peak."""


def read(ctx):
    w = ctx.window_s()
    if w <= 0:
        return None
    flops = ctx.flops.round_flops(ctx.cfg, ctx.traffic) * ctx.rounds
    return 100.0 * flops / w / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
