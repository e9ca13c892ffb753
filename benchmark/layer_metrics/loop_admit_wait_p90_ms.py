"""90th percentile of ``admit_wait``: row handed to the slot loop -> a slot
(the loop's FIFO, ring drains included)."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    return _slot_loop.phase_p90_ms(ctx, "admit_wait")
