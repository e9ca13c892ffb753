"""Megabytes (1e6 bytes) of weights that the slot loop's step and chunk
programs agreed to have relaid once, at set-up, so that neither transposes
them again in every run: ``weights_relaid_mb`` of ``SlotLoop.stats()``
(``Generator.slot_execs``; 0 where the compiler wanted every weight as it
lay).  None where the program keeps no such counter."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    return _slot_loop.stats(ctx).get("weights_relaid_mb")
