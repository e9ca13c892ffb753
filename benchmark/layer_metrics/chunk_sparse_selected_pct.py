"""Share of the causal columns that the selector lets the attention read
in the window's prefill chunks: ``chunk_attn_columns_selected /
chunk_attn_columns_valid`` of ``SlotLoop.counters``, over the chunks'
valid tokens (``sparse_selected_pct`` is the same over the steps' live
rows; 100 while every context is inside ``index_topk``)."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    c = _slot_loop.stats(ctx)
    if not c.get("chunk_attn_columns_valid"):
        return None
    return 100.0 * c["chunk_attn_columns_selected"] \
        / c["chunk_attn_columns_valid"]
