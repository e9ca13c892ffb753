"""Mean device ms a run that the training step spends under the
``optimizer`` bucket of its named scopes, forward and backward together
(``_program_scopes``; the printed line splits them)."""
from benchmark.layer_metrics import _program_scopes


def compute(ctx):
    return _program_scopes.bucket_ms(ctx, "step", "optimizer")
