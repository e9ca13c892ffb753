"""Share of the ring's column blocks that the decode steps' attention read
over the window: ``100 * attn_blocks_read / attn_blocks_total`` of
``SlotLoop.counters`` (per step the blocks from the oldest generating
row's ``start`` to the shared frontier, of those a plane has; the step
program's own arithmetic, ``cached_attention``).  None where the program
keeps no such counter."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    c = _slot_loop.stats(ctx)
    if not c.get("attn_blocks_total"):
        return None
    return 100.0 * c["attn_blocks_read"] / c["attn_blocks_total"]
