"""90th percentile of ``handoff``: a request's arrival at ``submit_decode``
-> its row handed to the slot loop (RequestQueue, pack, a free worker)."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    return _slot_loop.phase_p90_ms(ctx, "handoff")
