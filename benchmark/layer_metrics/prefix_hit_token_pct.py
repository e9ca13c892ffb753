"""Share of the prompt tokens admitted in the window that came out of the
prefix cache's blocks and passed through no prefill chunk: ``100 x
prefix_hit_tokens / prompt_tokens_admitted`` of ``SlotLoop.counters``.
None where the program keeps no such counters (the parent) or admitted
nothing."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    c = _slot_loop.stats(ctx)
    if "prefix_hit_tokens" not in c or not c.get("prompt_tokens_admitted"):
        return None
    return 100.0 * c["prefix_hit_tokens"] / c["prompt_tokens_admitted"]
