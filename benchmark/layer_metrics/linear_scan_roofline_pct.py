"""Roofline share of the linear layers' chunked scans: the larger of the
recurrence's operations over the average chunk's valid tokens
(``chunk_ssm_tokens / chunks``) and the row's states read and written once
a layer (``benchmark/counts/<family>.py``'s ``linear_scan``), over the
chunk program's device time under ``linear_attention/scan`` and in the
compiler's own copies of a whole state plane, if it makes any (as
``linear_update_roofline_pct``)."""
from benchmark.layer_metrics import _sala_scope


def compute(ctx):
    return _sala_scope.roofline_pct(
        ctx, "chunk", _sala_scope.LINEAR, ("scan", "plane_copy"),
        "linear_scan", "chunks", ("chunk_ssm_tokens",))
