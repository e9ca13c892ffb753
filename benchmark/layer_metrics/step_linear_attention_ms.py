"""Mean device ms a run that the slot loop's step program spends under the
scope component ``linear_attention`` (``_sala_scope``): the one-token
updates of the matrix states."""
from benchmark.layer_metrics import _sala_scope


def compute(ctx):
    return _sala_scope.ms(ctx, "step", _sala_scope.LINEAR)
