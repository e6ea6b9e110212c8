"""Host time per round in the `campaign.fetch` span (ms), the median over
the window's chunks: the host waits there for the chunk's device work,
so this is the slack in which the next chunk could be planned."""
import spans


def read(ctx):
    return spans.chunk_ms(ctx, "campaign.fetch")
