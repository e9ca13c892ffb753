"""90th percentile of ``reply_hold``: a request's row retired -> its Future
resolved (the Server worker's wait for the row's batch-mates)."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    return _slot_loop.phase_p90_ms(ctx, "reply_hold")
