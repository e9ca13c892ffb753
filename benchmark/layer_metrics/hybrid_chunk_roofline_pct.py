"""Roofline share of the prefill-chunk program of a decoder that keeps conv
states beside K/V planes: as ``hybrid_step_roofline_pct``, for the average
chunk of the window (its valid tokens, expert assignments and valid
(token, column) pairs from the slot loop's ``chunk_*`` counters) over the
chunk program's median device time."""
from benchmark.layer_metrics import hybrid_step_roofline_pct


def compute(ctx):
    return hybrid_step_roofline_pct.share(ctx, "chunk")
