"""Device time per round of aggregation (ms): the round program's ops
under the `aggregate` or `region_sync` named scopes, collectives and the
`wagg` kernel included, averaged over the chips."""
import spans


def read(ctx):
    return spans.scope_ms(ctx, "aggregate", "region_sync")
