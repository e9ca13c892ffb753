"""Roofline share of the latent decoder's prefill-chunk program: as
``latent_step_roofline_pct``, for the average chunk of the window (its
valid tokens, held assignments and selected / valid columns from the slot
loop's ``chunk_*`` counters) over the chunk program's median device time."""
from benchmark.layer_metrics import latent_step_roofline_pct


def compute(ctx):
    return latent_step_roofline_pct.share(ctx, "chunk")
