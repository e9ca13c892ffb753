"""Rows an expert held here computes per dispatch per MoE layer, on
average: held assignments / (steps + chunks) / MoE layers / experts held
(``SlotLoop.counters``).  The deployment's own figure is in the
configuration's ``deployment``."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    c, cfg = _slot_loop.stats(ctx), ctx["config"]
    n = c.get("steps", 0) + c.get("chunks", 0)
    if "moe_assignments_held" not in c or not n:
        return None
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    held = cfg["experts_held"][1] - cfg["experts_held"][0]
    return c["moe_assignments_held"] / n / layers / held
