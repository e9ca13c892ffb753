"""Roofline share of the state-space layers' one-token updates: the least
time the chip could take to read and write the states of the average
step's live rows (``ssm_rows_updated / steps`` rows x the state-space
layers x 2 x a row's state and convolution inputs, against the
recurrence's operations: ``benchmark/counts/<family>.py``'s
``state_update``) over the step program's device time under
``state_space/update``."""
from benchmark.layer_metrics import _state_space_scope


def compute(ctx):
    return _state_space_scope.roofline_pct(
        ctx, "step", "update", "state_update", "steps", "ssm_rows_updated")
