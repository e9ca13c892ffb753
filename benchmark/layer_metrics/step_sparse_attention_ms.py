"""Mean device ms a run that the slot loop's step program spends under the
scope component ``sparse_attention`` (``_sala_scope``): the pooled keys'
write, the pooled scores and the block choice, the read of the blocks."""
from benchmark.layer_metrics import _sala_scope


def compute(ctx):
    return _sala_scope.ms(ctx, "step", _sala_scope.SPARSE)
