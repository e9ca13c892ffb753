"""Share of the traced slice's device-idle seconds that fall under a span
of the slot loop's driver thread (``slots.SPAN_NAMES``): how much of the
idle time the program's own spans explain."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    gaps = _slot_loop.idle_by_driver_span(ctx)
    total = sum(gaps.values()) if gaps else 0.0
    if not total:
        return None
    return 100.0 * (1.0 - gaps.get("no span", 0.0) / total)
