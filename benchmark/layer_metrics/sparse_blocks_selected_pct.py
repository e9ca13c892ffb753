"""Share of their contexts' blocks that the sparse layers read:
``sparse_blocks_selected / sparse_blocks_valid`` of ``SlotLoop.counters``,
over the live rows of the window's decode steps (100 while every context
is inside ``dense_len``: nothing is chosen)."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    c = _slot_loop.stats(ctx)
    if not c.get("sparse_blocks_valid"):
        return None
    return 100.0 * c["sparse_blocks_selected"] / c["sparse_blocks_valid"]
