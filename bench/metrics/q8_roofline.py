"""Share of the HBM roofline reached by the int8 codec kernels
(kernels/qdelta.py) (%): the bytes the encode (f32 deltas and residuals
in; int8 codes, f32 scales and the new residual out) and decode (codes
and scales in, f32 out) calls read and write by their operand and result
shapes, over the HBM peak and the kernels' device time."""


def read(ctx):
    ns = nbytes = 0
    for d in ctx.trace["devices"]:
        ops = ctx.round_ops(d)
        ns += ctx.tr.class_ns(ops, "q8_encode", "q8_decode")
        nbytes += ctx.tr.class_bytes(ops, "q8_encode", "q8_decode")
    if not ns:
        return None
    return 100.0 * nbytes / ctx.peaks["hbm_bytes_per_s"] / (ns / 1e9)
