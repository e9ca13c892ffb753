"""Mean device ms a run that the slot loop's chunk program spends under the
scope component ``state_space`` (``_state_space_scope``)."""
from benchmark.layer_metrics import _state_space_scope


def compute(ctx):
    return _state_space_scope.ms(ctx, "chunk")
