"""Share of the causal columns that the full layers' selector lets the
attention read: ``attn_columns_selected / attn_columns_valid`` of
``SlotLoop.counters``, over the live rows of the window's decode steps (100
while every context is inside ``index_topk``: the selector does not
bind)."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    c = _slot_loop.stats(ctx)
    if not c.get("attn_columns_valid"):
        return None
    return 100.0 * c["attn_columns_selected"] / c["attn_columns_valid"]
