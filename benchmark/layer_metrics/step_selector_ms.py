"""Mean device ms a run that the slot loop's step program spends under the
scope component ``selector`` (``_selector_scope``): a part of
``step_attention_ms``."""
from benchmark.layer_metrics import _selector_scope


def compute(ctx):
    return _selector_scope.ms(ctx, "step")
