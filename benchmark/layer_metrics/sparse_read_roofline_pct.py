"""Roofline share of the sparse layers' step: the least time the chip
could take to read the CHOSEN blocks' keys and values and the pooled
entries scored of the average step (``sparse_blocks_selected / steps``
blocks and ``pooled_entries_scored / steps`` entries, a row a layer:
``benchmark/counts/<family>.py``'s ``sparse_read``) over the step
program's device time under ``sparse_attention/select`` + ``/read``.  The
same count whichever form reads (a gather of the blocks, or every valid
column under a mask)."""
from benchmark.layer_metrics import _sala_scope


def compute(ctx):
    return _sala_scope.roofline_pct(
        ctx, "step", _sala_scope.SPARSE, ("select", "read"), "sparse_read",
        "steps", ("sparse_blocks_selected", "pooled_entries_scored"))
