"""Share of the traced slice's busy device seconds that fall in a bucket of
``benchmark/scope_buckets.json``: how much of the device's work the
program's named scopes explain (``_program_scopes``)."""
from benchmark.layer_metrics import _program_scopes


def compute(ctx):
    return _program_scopes.scoped_pct(ctx)
