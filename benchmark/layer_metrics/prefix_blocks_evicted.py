"""Blocks the prefix cache evicted in the window to stay inside its budget
(``prefix_blocks_evicted`` of ``SlotLoop.counters``; 0 while the budget
holds everything published).  None where the program has no such counter
or no lookup was made (the cache is off)."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    c = _slot_loop.stats(ctx)
    if "prefix_blocks_evicted" not in c or not c.get("prefix_lookups"):
        return None
    return float(c["prefix_blocks_evicted"])
