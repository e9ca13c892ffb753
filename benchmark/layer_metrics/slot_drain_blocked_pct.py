"""Share of slot-steps in which the slot stood empty while the FIFO head
waited for the ring session to drain and restart."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    return _slot_loop.slot_steps_pct(ctx, "drain_blocked")
