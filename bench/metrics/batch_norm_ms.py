"""Device time per round of BatchNorm (ms): the round program's ops under
the `batch_norm` named scope, forward and transposed, averaged over the
chips. A fusion counts where its root op lies."""
import spans


def read(ctx):
    return spans.scope_ms(ctx, "batch_norm")
