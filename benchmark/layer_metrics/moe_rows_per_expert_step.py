"""Rows an expert computes in a decode step, on average: the steps' expert
assignments / steps / MoE layers / experts (``SlotLoop.counters``, for a
layer that holds every expert).  At ~240 rows (peak FLOP/s over peak
bytes/s) an expert's products cost as much as streaming its weights; below
it the step's expert layers are bound by the bandwidth.  None where the
program keeps no such counters."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    c, cfg = _slot_loop.stats(ctx), ctx["config"]
    if not c.get("steps") or "chunk_moe_assignments" not in c \
            or "num_dense_layers" not in cfg:
        return None
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    return (c["moe_assignments"] - c["chunk_moe_assignments"]) \
        / c["steps"] / layers / cfg["num_experts"]
