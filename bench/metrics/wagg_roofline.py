"""Share of the HBM roofline reached by the fused aggregation kernel
(kernels/wagg.py) (%): the bytes its calls read and write by their
operand and result shapes (the stacked (V, P) f32 models, the weights,
one (P,) model out), over the HBM peak and the kernel's device time."""


def read(ctx):
    ns = nbytes = 0
    for d in ctx.trace["devices"]:
        ops = ctx.round_ops(d)
        ns += ctx.tr.class_ns(ops, "wagg")
        nbytes += ctx.tr.class_bytes(ops, "wagg")
    if not ns:
        return None
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / (ns / 1e9)
