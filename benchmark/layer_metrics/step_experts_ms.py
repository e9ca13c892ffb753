"""Mean device ms a run that the slot loop's step program spends under the
``experts`` bucket of its named scopes (``_program_scopes``: ops' self time
inside whole runs ÷ those runs)."""
from benchmark.layer_metrics import _program_scopes


def compute(ctx):
    return _program_scopes.bucket_ms(ctx, "step", "experts")
