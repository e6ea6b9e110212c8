"""Share of the roofline reached by the client step's convolutions (%):
the least time the chip could take for a round's forward, input- and
weight-gradient convolutions (each bounded by the larger of FLOPs over
the bf16 peak and bytes over the HBM peak, flops/<model>.py), times the
rounds, over the device time of the ops XLA classes as convolutions,
summed over the chips."""


def read(ctx):
    conv_ns = sum(ctx.tr.class_ns(ctx.round_ops(d), "conv")
                  for d in ctx.trace["devices"])
    if not conv_ns:
        return None
    least, _, _ = ctx.flops.conv_roofline_s(
        ctx.cfg, ctx.traffic, ctx.peaks["bf16_flops_per_s"],
        ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.rounds / (conv_ns / 1e9)
