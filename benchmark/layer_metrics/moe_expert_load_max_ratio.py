"""How uneven the routing got: the largest number of rows one expert was
given in any one dispatch of the window (``moe_expert_tokens_max`` of
``SlotLoop.counters``) over the even share of the largest dispatch the
configuration makes (a full prefill chunk or a full step, whichever has
more tokens: tokens x experts per token / experts).  1 is even; the expert
layer pads each expert's rows to a multiple of the even share and leaves
the padded product when one expert passes it.  None where the program
keeps no such counter."""
from benchmark.layer_metrics import _slot_loop


def compute(ctx):
    c, cfg = _slot_loop.stats(ctx), ctx["config"]
    if "moe_expert_tokens_max" not in c or "num_experts" not in cfg:
        return None
    sv = cfg["serve"]
    tokens = max(int(sv["prefill_chunk"]), int(sv["slots"]))
    even = tokens * cfg["num_experts_per_tok"] / cfg["num_experts"]
    return c["moe_expert_tokens_max"] / even
