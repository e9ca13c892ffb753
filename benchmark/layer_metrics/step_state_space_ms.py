"""Mean device ms a run that the slot loop's step program spends under the
scope component ``state_space`` (``_state_space_scope``): the state-space
layers with their norms, projections and residual adds."""
from benchmark.layer_metrics import _state_space_scope


def compute(ctx):
    return _state_space_scope.ms(ctx, "step")
