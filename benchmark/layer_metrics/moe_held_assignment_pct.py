"""Share of the router's top-k choices that fell on experts held on this
chip: ``moe_assignments_held / moe_assignments`` of ``SlotLoop.counters``
over the live tokens of the window's steps and chunks (held / published
experts = 12.5% when the routing is even)."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    c = _slot_loop.stats(ctx)
    if not c.get("moe_assignments"):
        return None
    return 100.0 * c["moe_assignments_held"] / c["moe_assignments"]
