"""Host time per round spent moving the model between the state and the
scan carry (ms): the `campaign.carry` and `campaign.state` spans (under
handover, the per-leaf stack and unstack of the RSU models), the median
over the window's chunks."""
import spans


def read(ctx):
    return spans.chunk_ms(ctx, "campaign.carry", "campaign.state")
