"""Device time per round of the upload codec (ms): the round program's
ops under the `codec` named scope (the q8 kernels and the XLA around
them), averaged over the chips."""
import spans


def read(ctx):
    return spans.scope_ms(ctx, "codec")
