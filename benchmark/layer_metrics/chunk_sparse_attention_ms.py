"""Mean device ms a run that the slot loop's chunk program spends under the
scope component ``sparse_attention`` (``_sala_scope``)."""
from benchmark.layer_metrics import _sala_scope


def compute(ctx):
    return _sala_scope.ms(ctx, "chunk", _sala_scope.SPARSE)
