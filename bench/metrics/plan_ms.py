"""Host time per round of `run_campaign`'s planning (ms): the
`campaign.plan` span (host draws, handover's eager Eq. 11 weights, the
schedule uploads), the median over the window's chunks."""
import spans


def read(ctx):
    return spans.chunk_ms(ctx, "campaign.plan")
