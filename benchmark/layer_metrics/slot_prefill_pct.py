"""Share of slot-steps in which the slot held a row still prefilling
(admitted, its chunks or its activation ahead)."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    return _slot_loop.slot_steps_pct(ctx, "prefilling")
