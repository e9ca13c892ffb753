"""90th percentile of ``arrival_ttft``: a request's arrival at
``submit_decode`` -> its first token in the loop; what a streaming client
would see as time to first token (nothing streams yet)."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    return _slot_loop.phase_p90_ms(ctx, "arrival_ttft")
