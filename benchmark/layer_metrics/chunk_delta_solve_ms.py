"""Mean device ms a run that the slot loop's chunk program spends under the
scope component ``solve`` of a delta-rule layer's scan (``_delta_scope``):
the triangular systems that give the pseudo-values, all the layers'."""
from benchmark.layer_metrics import _delta_scope


def compute(ctx):
    return _delta_scope.ms(ctx, "chunk", "solve")
