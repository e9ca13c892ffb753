"""Roofline share of the linear layers' one-token updates: the least time
the chip could take to read and write the matrix states of the average
step's LIVE rows (``ssm_rows_updated / steps`` rows x the linear layers x
2 x 2,097,152 B, against the recurrence's operations:
``benchmark/counts/<family>.py``'s ``linear_update``) over the step
program's device time under ``linear_attention/update`` and in the
compiler's own copies of a whole state plane (the update writes each new
50 MB plane into the chip's other memory space and a copy that carries no
scope takes it to the output: the update's write;
``_sala_scope.plane_copy_ms``).  No other op without a scope is counted."""
from benchmark.layer_metrics import _sala_scope


def compute(ctx):
    return _sala_scope.roofline_pct(
        ctx, "step", _sala_scope.LINEAR, ("update", "plane_copy"),
        "linear_update", "steps", ("ssm_rows_updated",))
