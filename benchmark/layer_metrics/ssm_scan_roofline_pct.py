"""Roofline share of the state-space layers' chunked scans: the larger of
the recurrence's operations over the average chunk's valid tokens
(``chunk_ssm_tokens / chunks``) and the row's states read and written once
a layer (``benchmark/counts/<family>.py``'s ``scan``), over the chunk
program's device time under ``state_space/scan``."""
from benchmark.layer_metrics import _state_space_scope


def compute(ctx):
    return _state_space_scope.roofline_pct(
        ctx, "chunk", "scan", "scan", "chunks", "chunk_ssm_tokens")
