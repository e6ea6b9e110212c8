"""Device time per round of the client step (ms): the round program's ops
under the `blur` or `client_step` named scopes, averaged over the
chips."""
import spans


def read(ctx):
    return spans.scope_ms(ctx, "blur", "client_step")
